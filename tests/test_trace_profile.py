"""Host spans in the profiler's own trace (ADR 015 addendum): every test
that opens a ``jax.profiler`` session lives here, behind ONE
module-scoped capture on the CPU. A process holds one session at a
time and tier-1 runs ``--dist loadfile``, so one file means one worker
and one session.

The capture is taken the way ``perfbench/run.py`` ``trace_slice`` takes
it (``host_tracer_level = 1``, ``python_tracer_level = 0``) over a
traced MicroBatcher on a real SigEngine: one bypassed batch whose inline
host answer is made to block the loop for 50 ms, then one whole-batch
device call on an executor thread.
"""

import asyncio
import importlib.util
import os
import time
import warnings

import pytest

from maxmq_tpu.trace import PipelineTracer

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
BLOCK_S = 0.05


def _tool():
    spec = importlib.util.spec_from_file_location(
        "_trace_gaps", os.path.join(ROOT, "tools", "trace_gaps.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tool():
    return _tool()


async def _traffic(inline, whole) -> dict:
    from test_batcher import _one_batch

    # the loop ledger as ``Broker.serve`` starts it: this thread's
    # sections, and the selector timed (``maxmq.idle`` / ``maxmq.poll``)
    ledger = inline.tracer.loop
    ledger.attach(asyncio.get_running_loop())
    host = inline.engine.subscribers_host_batch

    def blocking_host(topics):
        time.sleep(BLOCK_S)         # the loop thread, held
        return host(topics)

    inline.engine.subscribers_host_batch = blocking_host
    try:
        inline._device_rtt, inline._rtt_samples = 10.0, 2
        bypassed = await _one_batch(inline)
        device = await _one_batch(whole, tag="w")
    finally:
        await inline.close()
        await whole.close()
        books = ledger.report()
        ledger.detach()
    assert bypassed.via == "host" and device.via == "whole"
    return {"bypassed": bypassed, "device": device, "books": books}


@pytest.fixture(scope="module")
def capture(tmp_path_factory, tool):
    import jax
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    from test_batcher import _traced_sig_batcher
    tracer = PipelineTracer(sample_n=1)
    inline = _traced_sig_batcher(tracer)
    whole = _traced_sig_batcher(tracer, pipeline_depth=1,
                                cpu_bypass=False)
    # the bucket's XLA compile stays out of the capture: a cold dispatch
    # would outlast the scripted block
    whole.engine.subscribers_fixed_batch([f"tr/{i}/w" for i in range(12)])
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        records = asyncio.run(_traffic(inline, whole))
    finally:
        jax.profiler.stop_trace()
    path = tool.xplane.find(trace_dir)
    assert path is not None, "the profiler wrote no trace"
    data = tool.xplane.load(path)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        threads = tool.host_threads(data)
    return {"path": path, "data": data, "threads": threads, **records}


def _named(threads, name):
    return [(k, ev) for k, events in enumerate(threads)
            for ev in events if ev[0] == name]


def test_batch_annotations_are_on_the_host_plane_with_their_stats(capture):
    rec = capture["bypassed"]
    ((line, (_name, lo, hi, stats)),) = _named(capture["threads"],
                                               "maxmq.batch")
    assert stats["batch"] == rec.id and stats["n"] == rec.n
    assert stats["via"] == "host"
    assert hi - lo >= BLOCK_S * 1e9
    # t0_ns is the tracer's clock at the span's start: the ring's
    # match_host starts there too
    host = next(p for p in rec.phases if p[0] == "match_host")
    assert stats["t0_ns"] == host[1]
    # the bypass's phases nest inside it, on the same line, same batch
    for phase in ("prep", "probe", "decode"):
        ((k, (_n, p0, p1, pst)),) = [
            hit for hit in _named(capture["threads"],
                                  f"maxmq.batch.{phase}")
            if hit[1][3]["batch"] == rec.id]
        assert k == line and lo <= p0 <= p1 <= hi
        assert "t0_ns" in pst
    assert _named(capture["threads"], "maxmq.settle")


def test_an_executor_batchs_phases_lie_on_another_line_than_the_loops(
        capture):
    rec = capture["device"]
    loop_line = _named(capture["threads"], "maxmq.batch")[0][0]
    lines = set()
    for phase in ("prep", "dispatch", "fetch", "decode"):
        hits = [hit for hit in _named(capture["threads"],
                                      f"maxmq.batch.{phase}")
                if hit[1][3]["batch"] == rec.id]
        assert len(hits) == 1, phase
        lines.add(hits[0][0])
    assert len(lines) == 1 and loop_line not in lines
    # a device batch has no enclosing annotation
    assert all(ev[3]["batch"] != rec.id
               for _k, ev in _named(capture["threads"], "maxmq.batch"))


def test_one_offset_carries_the_tracers_clock_to_the_profilers(capture):
    """Every annotation's t0_ns minus its start in the file is the same
    offset, to well under the shortest span of interest."""
    offsets = [ev[3]["t0_ns"] - ev[1] for events in capture["threads"]
               for ev in events if "t0_ns" in ev[3]]
    assert len(offsets) >= 8
    assert max(offsets) - min(offsets) < 500_000        # 0.5 ms


def test_trace_gaps_gives_the_inline_block_to_maxmq_batch(capture, tool):
    out = tool.analyse(capture["data"])
    assert out["device_planes"] == 0            # the CPU: all of it idle
    assert out["idle_s"] == pytest.approx(out["slice_s"])
    loop = out["groups"]["loop"]["names"]
    assert BLOCK_S <= loop["maxmq.batch"] < BLOCK_S + 0.05
    assert "maxmq.settle" in loop
    # nested phases are not top-level on the loop's thread...
    assert not [n for n in loop if n.startswith("maxmq.batch.")]
    # ...and are on the executor's, which has no enclosing annotation
    other = out["groups"]["other"]["names"]
    assert {"maxmq.batch.prep", "maxmq.batch.dispatch",
            "maxmq.batch.fetch", "maxmq.batch.decode"} <= set(other)
    assert out["gaps"][0]["covered_by"] == "loop:maxmq.batch"
    assert out["tracer_minus_profiler_clock_ns"] is not None
    tool.show(out)                               # prints, does not raise


def test_the_ledgers_books_agree_with_the_capture(capture, tool):
    """One site feeds both: the seconds ``tools/trace_gaps.py`` finds a
    name in the capture and the loop ledger's total for that state are
    the same length, and the selector's waits are rows of the tool."""
    seconds = tool.analyse(capture["data"])["groups"]["loop"]["seconds"]
    books = capture["books"]
    assert books["wrapped"]
    assert books["seconds"]["batch"] >= BLOCK_S
    assert seconds["maxmq.batch"] == pytest.approx(
        books["seconds"]["batch"], rel=0.02)
    assert "maxmq.settle" in seconds and books["entries"]["settle"] == 2
    # the waits for the executor's batch are idle time in both
    assert books["entries"]["idle"] >= 1
    assert seconds["maxmq.idle"] == pytest.approx(
        books["seconds"]["idle"], rel=0.05, abs=50e-6)
    assert "maxmq.poll" in seconds


def test_interval_arithmetic_on_made_up_events(tool):
    """No profiler: the attribution on events written by hand."""
    assert tool.merge([(5, 9), (0, 2), (1, 3), (9, 9)]) == [(0, 3), (5, 9)]
    assert tool.complement([(2, 4), (6, 8)], 0, 10) == \
        [(0, 2), (4, 6), (8, 10)]
    assert tool.complement([], 3, 7) == [(3, 7)]
    assert tool.complement([(0, 10)], 0, 10) == []
    assert tool.overlap([(0, 4), (6, 9)], [(2, 7), (8, 20)]) == 2 + 1 + 1
    # nested events are not top-level; siblings are
    events = [("maxmq.batch", 10, 50), ("maxmq.batch.prep", 12, 20),
              ("maxmq.settle", 50, 55), ("maxmq.read", 70, 90)]
    assert tool.top_level(events) == [events[0], events[2], events[3]]
    # device busy 20..30 and 60..70 of a slice 0..100
    idle = tool.complement([(20, 30), (60, 70)], 0, 100)
    loop = tool.top_level(events)
    worker = [("maxmq.batch.fetch", 0, 25)]
    got = tool.attribute(idle, tool.spans_by_name([loop]))
    assert got["names"] == {"maxmq.batch": 30e-9, "maxmq.settle": 5e-9,
                            "maxmq.read": 20e-9}
    assert got["unannotated"] == pytest.approx((80 - 55) * 1e-9)
    groups = {"loop": tool.spans_by_name([loop]),
              "other": tool.spans_by_name([worker])}
    assert tool.covering((0, 20), groups) == "other:maxmq.batch.fetch"
    assert tool.covering((30, 60), groups) == "loop:maxmq.batch"
    assert tool.covering((95, 100), groups) == "unannotated"
    # a batch's dispatch and fetch pair up by id, whatever the thread
    threads = [[("maxmq.batch.dispatch", 5, 6, {"batch": 3}),
                ("maxmq.batch.fetch", 8, 9, {"batch": 3}),
                ("maxmq.batch.dispatch", 20, 21, {"batch": 4})],
               [("maxmq.batch.fetch", 30, 31, {"batch": 4}),
                ("maxmq.read", 1, 2, {})]]
    assert tool.round_trips(threads) == [(5, 9), (20, 31)]
    assert tool.loop_thread(threads) == 1
    assert tool.loop_thread([threads[0]]) is None


def test_a_puback_inside_a_read_gets_a_row_of_its_own(tool):
    """``maxmq.ack`` is opened inside a chunk's ``maxmq.read``: its time
    is cut out of the span around it, which keeps the rest; a burst's
    ``maxmq.flush`` inside it is cut out of the ack in turn (self time
    at every depth, as the loop ledger's stack gives it)."""
    events = [("maxmq.read", 0, 100), ("maxmq.ack", 10, 20),
              ("maxmq.ack", 30, 45), ("maxmq.flush", 12, 14),
              ("maxmq.deliver", 120, 150), ("maxmq.ack", 200, 210)]
    carved = tool.carve(events)
    assert carved == [("maxmq.read", 0, 10), ("maxmq.ack", 10, 12),
                      ("maxmq.flush", 12, 14), ("maxmq.ack", 14, 20),
                      ("maxmq.read", 20, 30), ("maxmq.ack", 30, 45),
                      ("maxmq.read", 45, 100), ("maxmq.deliver", 120, 150),
                      ("maxmq.ack", 200, 210)]
    got = tool.attribute([(0, 300)], tool.spans_by_name([carved]))
    assert got["names"] == {"maxmq.read": 75e-9, "maxmq.ack": 33e-9,
                            "maxmq.flush": 2e-9, "maxmq.deliver": 30e-9}
    assert got["unannotated"] == pytest.approx(160e-9)
    # with nothing carved, a thread's events are its top-level ones
    plain = [e for e in events if e[0] not in tool.CARVED]
    assert tool.carve(plain) == tool.top_level(plain)


def test_the_share_picks_inside_a_deliver_get_a_row_of_their_own(tool):
    """``maxmq.share`` is opened inside a publish's ``maxmq.deliver``
    (or, on the trie path, inside the chunk's ``maxmq.read``): the picks
    are cut out of the span around them, beside the PUBACKs."""
    events = [("maxmq.deliver", 0, 100), ("maxmq.share", 5, 40),
              ("maxmq.flush", 60, 90),
              ("maxmq.read", 200, 300), ("maxmq.ack", 210, 220),
              ("maxmq.share", 230, 250), ("maxmq.deliver", 400, 420)]
    carved = tool.carve(events)
    assert carved == [("maxmq.deliver", 0, 5), ("maxmq.share", 5, 40),
                      ("maxmq.deliver", 40, 60), ("maxmq.flush", 60, 90),
                      ("maxmq.deliver", 90, 100), ("maxmq.read", 200, 210),
                      ("maxmq.ack", 210, 220), ("maxmq.read", 220, 230),
                      ("maxmq.share", 230, 250), ("maxmq.read", 250, 300),
                      ("maxmq.deliver", 400, 420)]
    got = tool.attribute([(0, 500)], tool.spans_by_name([carved]))
    assert got["names"] == {"maxmq.deliver": 55e-9, "maxmq.share": 55e-9,
                            "maxmq.flush": 30e-9, "maxmq.read": 70e-9,
                            "maxmq.ack": 10e-9}
    assert got["unannotated"] == pytest.approx(280e-9)


def test_a_pass_keeps_its_own_python_and_the_loops_waits_have_rows(tool):
    """``maxmq.pass`` is a flush pass whole: its bursts' ``maxmq.flush``
    are cut out of it, as ``maxmq.ack`` out of ``maxmq.read``, also where
    the pass itself runs inside another section. The selector's
    ``maxmq.idle`` and ``maxmq.poll`` are rows like any other, so
    ``unannotated`` is busy time alone; ``seconds`` is each name whole,
    not cut to the device's idle time, inside the slice asked for."""
    events = [("maxmq.idle", 0, 100), ("maxmq.poll", 100, 104),
              ("maxmq.pass", 110, 200), ("maxmq.flush", 120, 140),
              ("maxmq.flush", 150, 180), ("maxmq.idle", 220, 300),
              ("maxmq.read", 310, 400), ("maxmq.pass", 350, 390),
              ("maxmq.flush", 360, 380)]
    carved = tool.carve(events)
    assert carved == [
        ("maxmq.idle", 0, 100), ("maxmq.poll", 100, 104),
        ("maxmq.pass", 110, 120), ("maxmq.flush", 120, 140),
        ("maxmq.pass", 140, 150), ("maxmq.flush", 150, 180),
        ("maxmq.pass", 180, 200), ("maxmq.idle", 220, 300),
        ("maxmq.read", 310, 350), ("maxmq.pass", 350, 360),
        ("maxmq.flush", 360, 380), ("maxmq.pass", 380, 390),
        ("maxmq.read", 390, 400)]
    # the device busy 0..50: half of the first wait is not its idle time
    got = tool.attribute([(50, 400)], tool.spans_by_name([carved]))
    assert got["names"]["maxmq.idle"] == 130e-9
    assert got["seconds"] == {
        "maxmq.idle": 180e-9, "maxmq.poll": 4e-9, "maxmq.pass": 60e-9,
        "maxmq.flush": 70e-9, "maxmq.read": 50e-9}
    assert got["unannotated"] == pytest.approx(36e-9)
    # cut to a slice: what lies outside it is nobody's
    cut = tool.attribute([(150, 360)],
                         tool.spans_by_name([carved], 150, 360))
    assert cut["seconds"] == {
        "maxmq.idle": 80e-9, "maxmq.poll": 0.0, "maxmq.pass": 30e-9,
        "maxmq.flush": 30e-9, "maxmq.read": 40e-9}
