"""The event loop's own books (ADR 015 addendum, ISSUE 36): the
``LoopLedger`` a ``PipelineTracer`` owns. Self time by construction
under a scripted clock, the selector wrapper (idle / poll) and its
restoring, the trailing span a sampled publish carries and
the benchmark's reader reads, the thread guard, the cost contract with
sampling off, and the exporter's four families."""

import asyncio
import json
import os
import sys
import threading
from types import SimpleNamespace

import pytest

from test_broker_system import connect, running_broker
from test_trace import _checker, poll

from maxmq_tpu import faults, trace
from maxmq_tpu.metrics import Registry, _register_trace_metrics
from maxmq_tpu.trace import (CRITICAL_STAGES, LOOP_SECTIONS,
                             LOOP_STAGES, LOOP_STATES, NO_SPAN, STAGES,
                             PipelineTracer)

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
SPAN_NS, SNAP_NS = trace.LEDGER_SPAN_NS, trace.LEDGER_SNAP_NS   # the real
TIMED = ("loop_busy", "loop_idle", "loop_offcpu", "loop_poll", "loop_other")


@pytest.fixture(autouse=True)
def clean_clock(monkeypatch):
    """A span of one second and snapshots 10 ms apart: the scripted
    timelines below are written to that scale."""
    monkeypatch.setattr(trace, "LEDGER_SPAN_NS", 1_000_000_000)
    monkeypatch.setattr(trace, "LEDGER_SNAP_NS", 10_000_000)
    yield
    faults.REGISTRY.reset_clock()


def test_the_span_is_long_against_a_generation_of_a_closed_loop():
    """0.4 s in the fan-in and fan-out cells: a span that ends at the
    head of a burst holds a whole number of them, rounded down."""
    assert SPAN_NS == 5_000_000_000 and SNAP_NS * 50 <= SPAN_NS


class Clock:
    """The fault registry's clock, moved by hand (nanoseconds)."""

    def __init__(self, start: int = 5_000_000_000) -> None:
        self.ns = start
        faults.REGISTRY.clock_ns = lambda: self.ns

    def tick(self, ns: int) -> None:
        self.ns += ns


class Selector:
    """What a stock selector loop holds: ``select`` is the class's own
    method until somebody wraps the instance's."""

    def __init__(self, clock: Clock, sleeps: int = 0) -> None:
        self.clock, self.sleeps, self.calls = clock, sleeps, []

    def select(self, timeout=None):
        self.calls.append(timeout)
        self.clock.tick(self.sleeps)
        return []


def bound(sample_n: int = 1) -> tuple:
    """A tracer whose ledger knows this thread, a scripted clock."""
    clock = Clock()
    tracer = PipelineTracer(sample_n=sample_n)
    tracer.loop.tid = threading.get_ident()
    return tracer, tracer.loop, clock


def seconds(ledger) -> dict:
    return {s: round(v * 1e9) for s, v in
            ledger.report()["seconds"].items()}


# -- self time ---------------------------------------------------------


@pytest.mark.parametrize("parent,child", [
    ("read", "ack"), ("deliver", "share"), ("pass", "flush"),
    ("read", "pass")])
def test_a_nested_section_takes_exactly_its_time_from_its_parent(
        parent, child):
    tracer, ledger, clock = bound()
    with tracer.section(parent):
        clock.tick(700)
        with tracer.section(child):
            clock.tick(250)
        clock.tick(50)
        with tracer.section(child):
            clock.tick(1000)
    got = seconds(ledger)
    assert got[parent] == 750 and got[child] == 1250
    assert ledger.report()["entries"][parent] == 1
    assert ledger.report()["entries"][child] == 2
    # three deep: the innermost is nobody else's
    with tracer.section("read"):
        with tracer.section("pass"):
            clock.tick(10)
            with tracer.section("flush"):
                clock.tick(7)
            clock.tick(1)
    after = seconds(ledger)
    assert after["flush"] - got["flush"] == 7
    assert after["pass"] - got["pass"] == 11
    assert after["read"] == got["read"]


def test_the_books_balance_to_the_nanosecond():
    """sum(sections) + other + poll = busy and busy + idle = wall,
    whatever the order of sections, waits and plain callbacks."""
    tracer, ledger, clock = bound()
    selector = Selector(clock, sleeps=400)
    ledger.attach(SimpleNamespace(_selector=selector))
    clock.tick(77)                      # before the books open: nobody's
    opened = clock.ns
    select = selector.select
    select(None)                        # the first transition opens them
    for k, name in enumerate(LOOP_SECTIONS):
        clock.tick(30 + k)              # a callback no section names
        with tracer.section(name):
            clock.tick(100 * (k + 1))
            if name == "read":
                with tracer.section("ack"):
                    clock.tick(9)
        select(0 if k % 2 else 0.25)    # 400 each: poll, idle, poll ...
    got = seconds(ledger)
    sections = sum(got[s] for s in LOOP_SECTIONS)
    assert got["ack"] == 100 * (LOOP_SECTIONS.index("ack") + 1) + 9
    assert got["other"] == sum(30 + k for k in range(len(LOOP_SECTIONS)))
    assert got["poll"] == 400 * 4 and got["idle"] == 400 * 5
    busy = sections + got["other"] + got["poll"]
    assert busy + got["idle"] == clock.ns - opened
    assert ledger.report()["turns"] == 9
    assert selector.calls == [None, 0.25, 0, 0.25, 0, 0.25, 0, 0.25, 0]


def test_a_section_on_another_thread_leaves_the_ledger_unmoved(monkeypatch):
    """``sig.py``'s rotation work and the journal's writer annotate for
    the profiler from threads of their own: the ledger ignores them."""
    built = []

    class Annotation:
        def __init__(self, name, **stats):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_annotation", Annotation)
    tracer, ledger, clock = bound()
    before = (list(ledger.ns), list(ledger.entries))

    def elsewhere():
        with tracer.section("flush", bufs=3):
            clock.tick(500)

    t = threading.Thread(target=elsewhere)
    t.start()
    t.join(5)
    assert not t.is_alive()
    assert built == ["maxmq.flush"]             # the profiler's alone
    assert (ledger.ns, ledger.entries) == before
    with tracer.section("flush", bufs=3):       # the loop's own thread
        clock.tick(500)
    assert seconds(ledger)["flush"] == 500 and built == ["maxmq.flush"] * 2


async def test_annotated_closes_the_section_at_every_real_suspension():
    tracer, ledger, clock = bound()
    gate = asyncio.get_running_loop().create_future()

    async def work():
        clock.tick(40)
        got = await gate
        clock.tick(60)
        return got

    task = asyncio.ensure_future(trace.annotated(tracer, "read", work()))
    await asyncio.sleep(0)
    assert ledger._stack == [] and seconds(ledger)["read"] == 40
    clock.tick(10_000)                  # somebody else's time
    gate.set_result("b")
    assert await task == "b"
    assert seconds(ledger)["read"] == 100
    assert ledger.report()["entries"]["read"] == 2
    assert ledger._stack == []
    # a cancellation thrown in at the suspension passes through it too

    async def waits():
        await asyncio.sleep(30)

    task = asyncio.ensure_future(trace.annotated(tracer, "read", waits()))
    await asyncio.sleep(0)
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    assert ledger._stack == [] and ledger.report()["entries"]["read"] == 4


# -- the selector ------------------------------------------------------


@pytest.mark.parametrize("timeout,state", [
    (0, "poll"), (0.0, "poll"), (None, "idle"), (0.5, "idle"),
    (-1, "idle")])
def test_the_wrapper_books_a_select_by_its_timeout(timeout, state):
    tracer, ledger, clock = bound()
    selector = Selector(clock, sleeps=1234)
    ledger.attach(SimpleNamespace(_selector=selector))
    assert ledger.wrapped and "select" in selector.__dict__
    selector.select(0)                  # opens the books
    base = seconds(ledger)
    selector.select(timeout)
    got = seconds(ledger)
    assert got[state] - base[state] == 1234
    other = "idle" if state == "poll" else "poll"
    assert got[other] == base[other]
    assert selector.calls == [0, timeout]
    ledger.detach()
    assert "select" not in selector.__dict__ and not ledger.wrapped
    selector.select(timeout)            # the class's own again
    assert seconds(ledger) == {s: got[s] for s in LOOP_SECTIONS}


def test_two_tracers_on_one_loop_unwrap_in_any_order():
    clock = Clock()
    selector = Selector(clock, sleeps=10)
    loop = SimpleNamespace(_selector=selector)
    a, b = PipelineTracer(sample_n=1), PipelineTracer(sample_n=1)
    a.loop.attach(loop)
    b.loop.attach(loop)
    selector.select(0)
    selector.select(None)
    assert a.loop.entries[:2] == b.loop.entries[:2] == [1, 1]
    a.loop.detach()                     # the inner one first
    selector.select(None)
    assert a.loop.entries[:2] == [1, 1] and b.loop.entries[:2] == [2, 1]
    b.loop.detach()
    selector.select(None)               # a's wrapper only passes through
    assert a.loop.entries[:2] == [1, 1] and b.loop.entries[:2] == [2, 1]
    assert selector.calls == [0, None, None, None]


def test_switched_off_the_books_pause_and_resume_without_the_gap():
    tracer, ledger, clock = bound()
    selector = Selector(clock, sleeps=100)
    ledger.attach(SimpleNamespace(_selector=selector))
    selector.select(None)
    selector.select(None)
    tracer.sample_n = 0
    clock.tick(1_000_000)
    selector.select(None)               # unbooked: pauses the books
    clock.tick(1_000_000)
    tracer.sample_n = 1
    selector.select(None)               # books its own 100 alone
    assert seconds(ledger)["idle"] == 300 and seconds(ledger)["other"] == 0


async def test_a_served_brokers_selector_is_wrapped_and_restored():
    async with running_broker(trace_sample_n=1) as broker:
        selector = broker.loop._selector
        assert "select" in selector.__dict__ and broker.tracer.loop.wrapped
        assert broker.tracer.loop.tid == threading.get_ident()
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#", qos=1)
        pub = await connect(broker, "p1")
        for i in range(6):
            await pub.publish(f"t/{i}", b"m", qos=1)
            await sub.next_message(timeout=3)
        ledger = broker.tracer.loop
        await poll(lambda: ledger.entries[LOOP_STATES.index("ack")] == 6,
                   what="the subscriber's six PUBACKs")
        books = broker.tracer.report()["loop"]
        # a pass whole, its writevs cut out of it; a PUBACK inside a read
        for state in ("read", "pass", "flush", "ack", "idle", "poll",
                      "other"):
            assert books["seconds"][state] > 0, state
        assert books["entries"]["pass"] >= 6
        assert books["entries"]["flush"] >= books["entries"]["pass"]
        assert books["entries"]["ack"] == 6
        assert books["turns"] == (books["entries"]["idle"]
                                  + books["entries"]["poll"])
        sys_topics = broker.tracer.sys_entries()
        assert sys_topics["$SYS/broker/trace/loop/read_seconds"] > 0
        assert sys_topics["$SYS/broker/trace/loop/turns"] >= books["turns"]
        await pub.disconnect()
        await sub.disconnect()
    assert "select" not in selector.__dict__
    assert not broker.tracer.loop.wrapped


# -- the sampled publish's span -----------------------------------------


def _entry(tracer, clock):
    tr = tracer.sample("t", 0, "c")
    tracer.finish(tr)
    return tr.entry


def _loop_spans(entry) -> dict:
    return {s["stage"]: s for s in entry["spans"]
            if s["stage"] in LOOP_STAGES}


def test_the_trailing_span_divides_by_the_publishes_admitted():
    """Every publish is a turn of the loop here: a read, then a select.
    Snapshots are cut where a turn ends, and a sampled publish carries
    the newest one against the newest a second older than it."""
    tracer, ledger, clock = bound(sample_n=4)
    selector = Selector(clock, sleeps=0)
    ledger.attach(SimpleNamespace(_selector=selector))
    cpu = [0]
    ledger.cpu_clock = lambda: cpu[0]
    selector.select(0)                          # snapshot 0, at t = 0

    def publishes(n, read_ns, ack_ns=0, idle_ns=0, cpu_ns=0):
        """n turns of one publish each; the entry of the last one
        sampled among them."""
        entry = None
        selector.sleeps = idle_ns
        for _ in range(n):
            with tracer.section("read"):
                clock.tick(read_ns)
                if ack_ns:
                    with tracer.section("ack"):
                        clock.tick(ack_ns)
                tr = tracer.sample("t", 0, "c")
            if tr is not None:
                tracer.finish(tr)
                entry = tr.entry
            cpu[0] += cpu_ns
            selector.select(None)               # snapshot k, then sleeps
        return entry

    # nothing before a snapshot is a second old: 16 turns of 51 ms
    first = publishes(8, 50_000_000, ack_ns=1_000_000)
    assert _loop_spans(first) == {}
    second = publishes(8, 50_000_000, ack_ns=1_000_000)
    assert _loop_spans(second) == {}
    # 12 turns of 60 ms: publish 28 is sampled with snapshot 27 the
    # newest (1.466 s), and the newest a second older is 9's (0.459 s)
    third = publishes(12, 50_000_000, idle_ns=10_000_000, cpu_ns=45_000_000)
    spans = _loop_spans(third)
    assert set(spans) == {"loop_read", "loop_ack", *TIMED}
    admitted = 27 - 9
    read_ns = admitted * 50_000_000
    assert spans["loop_read"]["dur_us"] == pytest.approx(
        read_ns / admitted / 1000, abs=0.05)
    assert spans["loop_read"]["calls"] == 1.0
    # ack ran in publishes 10..16 of the 18 between the two snapshots
    assert spans["loop_ack"]["dur_us"] == pytest.approx(
        7 * 1_000_000 / admitted / 1000, abs=0.05)
    assert spans["loop_ack"]["calls"] == 0.389
    # the selects after publishes 17..26 slept inside the span
    assert spans["loop_idle"]["dur_us"] == pytest.approx(
        10 * 10_000_000 / admitted / 1000, abs=0.05)
    assert spans["loop_idle"]["calls"] == 1.0
    busy = read_ns + 7 * 1_000_000
    assert spans["loop_busy"]["dur_us"] == pytest.approx(
        busy / admitted / 1000, abs=0.05)
    assert spans["loop_busy"]["calls"] == 1.0   # a turn a publish
    # off-CPU: busy wall time the thread's CPU clock did not see
    assert spans["loop_offcpu"]["dur_us"] == pytest.approx(
        (busy - 11 * 45_000_000) / admitted / 1000, abs=0.05)
    assert "calls" not in spans["loop_offcpu"]
    assert spans["loop_other"]["dur_us"] == 0 == spans["loop_poll"]["dur_us"]
    for span in spans.values():
        assert span["parent"] == "" and span["off_us"] == 0
        assert round(span["dur_us"], 1) == span["dur_us"]
    # a known state that did nothing in the span reports a true 0
    later = publishes(40, 50_000_000)
    assert _loop_spans(later)["loop_ack"]["dur_us"] == 0
    assert _loop_spans(later)["loop_ack"]["calls"] == 0
    # and one never entered reports nothing
    assert "loop_deliver" not in _loop_spans(later)


def test_a_span_holds_whole_turns_however_a_turns_publishes_bunch():
    """Publishes are admitted in bursts, a turn's reads together. A
    span cut at a sampled publish would start at the end of a burst and
    end inside one, and read a publish dearer than it is; cut between
    turns it holds whole turns, and every sampled publish of a steady
    loop reads the same."""
    tracer, ledger, clock = bound(sample_n=1)
    selector = Selector(clock)
    ledger.attach(SimpleNamespace(_selector=selector))
    selector.select(0)
    entries = []
    for _turn in range(12):                     # 300 ms a turn
        for _ in range(10):                     # its burst of ten reads
            with tracer.section("read"):
                clock.tick(1_000_000)
                tr = tracer.sample("t", 0, "c")
            tracer.finish(tr)
            entries.append(tr.entry)
        with tracer.section("deliver"):         # and the rest of the turn
            clock.tick(290_000_000)
        selector.select(0)
    got = {e["id"]: _loop_spans(e)["loop_busy"]["dur_us"]
           for e in entries if _loop_spans(e)}
    assert len(got) >= 70
    assert set(got.values()) == {30_000.0}      # 300 ms over ten, always


def test_more_cpu_than_busy_time_floors_offcpu_at_zero():
    tracer, ledger, clock = bound()
    selector = Selector(clock)
    ledger.attach(SimpleNamespace(_selector=selector))
    cpu = [0]
    ledger.cpu_clock = lambda: cpu[0]
    selector.select(0)
    for _ in range(4):
        with tracer.section("read"):
            entry = _entry(tracer, clock)
            clock.tick(600_000_000)
            cpu[0] += 700_000_000
        selector.select(0)
    assert _loop_spans(entry)["loop_offcpu"]["dur_us"] == 0
    assert _loop_spans(entry)["loop_busy"]["dur_us"] == 600_000.0


def test_the_cpu_of_an_idle_select_is_no_busy_states():
    """The kernel's work inside a select that sleeps is on the thread's
    CPU clock; it is taken out, or an idle loop would read as on-CPU
    for longer than it was busy."""
    tracer, ledger, clock = bound()
    cpu = [0]

    class Sleeps(Selector):
        def select(self, timeout=None):
            cpu[0] += 4_000             # the system call's own CPU
            return super().select(timeout)

    selector = Sleeps(clock, sleeps=200_000_000)
    ledger.attach(SimpleNamespace(_selector=selector))
    ledger.cpu_clock = lambda: cpu[0]
    selector.select(0)
    for _ in range(8):
        with tracer.section("read"):
            clock.tick(1_000_000)
            cpu[0] += 900_000
            entry = _entry(tracer, clock)
        selector.select(None)
    spans = _loop_spans(entry)
    # 1 ms of read a publish, 0.9 of it on the CPU: 100 us off it
    assert spans["loop_offcpu"]["dur_us"] == pytest.approx(100.0, abs=0.2)
    assert spans["loop_idle"]["dur_us"] == 200_000.0
    assert ledger.idle_cpu_ns == 8 * 4_000


def test_without_a_selector_the_timed_states_are_absent_never_zero():
    """uvloop, or a loop with no ``_selector``: nothing is wrapped, and
    busy, idle, poll, other and offcpu are absent; sections are kept."""
    tracer, ledger, clock = bound()
    ledger.attach(SimpleNamespace())            # no _selector at all
    assert not ledger.wrapped
    for _ in range(3):
        with tracer.section("read"):
            clock.tick(500_000_000)
            entry = _entry(tracer, clock)
    spans = _loop_spans(entry)
    assert set(spans) == {"loop_read"}
    books = ledger.report()
    assert set(books["seconds"]) == set(LOOP_SECTIONS)
    assert not books["wrapped"] and books["turns"] == 0


@pytest.mark.parametrize("stage", LOOP_STAGES)
def test_a_loop_stage_is_a_stage_beside_the_path(stage):
    """In the stage list (the benchmark's reader looks a stage up
    there), outside the critical path, with no seconds histogram."""
    assert stage in STAGES and stage not in CRITICAL_STAGES
    assert stage not in PipelineTracer().stage_hist
    assert stage == "loop_" + stage[5:] and (
        stage[5:] in LOOP_STATES or stage[5:] in ("busy", "offcpu"))


def test_loop_spans_stay_out_of_the_chrome_export_and_the_histograms():
    tracer, ledger, clock = bound()
    for _ in range(3):
        with tracer.section("read"):
            clock.tick(500_000_000)
            entry = _entry(tracer, clock)
    assert _loop_spans(entry)
    names = {e["name"] for e in tracer.chrome_events()["traceEvents"]}
    assert not names & set(LOOP_STAGES)
    assert all(h.count == 0 for s, h in tracer.stage_hist.items())


# -- the benchmark's reader ---------------------------------------------


def _layers() -> dict:
    root = os.path.join(ROOT, "perfbench", "layers")
    out = {}
    for name in sorted(os.listdir(root)):
        if name.startswith("loop_") and "_us." in name:
            with open(os.path.join(root, name)) as fh:
                out[name[:-len(".json")]] = json.load(fh)
    return out


def test_the_eleven_metrics_and_their_files_agree():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    layers = _layers()
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"] in layers}
    assert len(layers) == len(entries) == 11
    closed = [w["name"] for w in bench["workloads"]
              if w["name"] not in ("fleet-1m.steady",
                                   "sparkplug-plant.steady")]
    for name, layer in layers.items():
        stage, suffix = name.split("_us.")
        entry = entries[name]
        assert layer == {"layer": "event loop", "moves": entry["moves"],
                         "unit": "us", "reader": "ring_stage_median",
                         "args": {"stage": stage}}
        assert stage in LOOP_STAGES
        assert (entry["source"], entry["better"], entry["unit"],
                entry["layer"]) == ("program_span", "lower", "us",
                                    "event loop")
        if suffix == "rate":
            assert entry["moves"] == "delivered_rate"
            assert entry["workloads"] == closed
        else:
            assert suffix == "latency"
            assert entry["moves"] == "deliver_p50_ms"
            assert entry["workloads"] == ["fleet-1m.steady",
                                          "sparkplug-plant.steady"]
    # what was there is there still, in its place: the eleven came last,
    # in one run and in this order (what later PRs add comes after them)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(next(iter(entries)))
    assert names[first:first + 11] == list(entries)
    assert not any(n.startswith("loop_") and "_us." in n
                   for n in names[first + 11:])


def test_the_benchmarks_reader_reads_a_ledger_span_through_its_file():
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    try:
        import readers
    finally:
        sys.path.pop(0)
    tracer, ledger, clock = bound()
    selector = Selector(clock)
    ledger.attach(SimpleNamespace(_selector=selector))
    selector.select(0)
    for _ in range(5):
        with tracer.section("read"):
            clock.tick(250_000_000)
            _entry(tracer, clock)
        selector.select(0)
    run = {"ring": tracer.report()["entries"]}
    layer = _layers()["loop_busy_us.rate"]
    assert readers.ring_stage_median(run, **layer["args"]) == \
        pytest.approx(250_000.0)
    # a stage the ledger never fed, and a ring of the parent's shape,
    # read as absent
    layer = _layers()["loop_deliver_us.rate"]
    assert readers.ring_stage_median(run, **layer["args"]) is None
    old = {"ring": [{"spans": [{"stage": "loop_lag", "dur_us": 9}],
                     "drains": []}]}
    assert readers.ring_stage_median(old, stage="loop_busy") is None


# -- off, and the exporter ----------------------------------------------


async def test_sampling_off_moves_no_state_and_wraps_nothing():
    async with running_broker() as broker:      # default: tracing off
        tracer, ledger = broker.tracer, broker.tracer.loop
        assert "select" not in broker.loop._selector.__dict__
        assert tracer.section("read") is NO_SPAN
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#", qos=1)
        pub = await connect(broker, "p1")
        for i in range(10):
            await pub.publish("t/x", b"m", qos=1)
        await sub.next_message(timeout=3)
        assert tracer.allocations == 0
        assert ledger.ns == [0] * len(LOOP_STATES)
        assert ledger.entries == [0] * len(LOOP_STATES)
        assert not ledger._marks and not ledger._stack
        assert not ledger.wrapped
        # switched on after serve: sections are kept, nothing is wrapped
        tracer.sample_n = 1
        await pub.publish("t/y", b"m", qos=1)
        await poll(lambda: ledger.entries[LOOP_STATES.index("flush")],
                   what="a flush section")
        assert "select" not in broker.loop._selector.__dict__
        assert set(tracer.report()["loop"]["seconds"]) == set(LOOP_SECTIONS)
        await pub.disconnect()
        await sub.disconnect()


async def test_the_loop_families_pass_the_exposition_check():
    checker = _checker()
    async with running_broker(trace_sample_n=1) as broker:
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#", qos=1)
        pub = await connect(broker, "p1")
        for i in range(4):
            await pub.publish(f"t/{i}", b"m", qos=1)
            await sub.next_message(timeout=3)
        reg = Registry()
        _register_trace_metrics(reg, broker)
        text = reg.expose()
        assert checker.validate(text) == []
        for state in LOOP_STATES:
            assert f'maxmq_loop_seconds_total{{state="{state}"}}' in text
        for state in LOOP_STATES:
            assert (f'maxmq_loop_entries_total{{state="{state}"}}' in text) \
                == (state != "other")
        for family in ("maxmq_loop_cpu_seconds_total",
                       "maxmq_loop_turns_total"):
            assert f"# TYPE {family} counter" in text
        turns = float(next(line.split()[1] for line in text.splitlines()
                           if line.startswith("maxmq_loop_turns_total ")))
        assert turns >= 4
        await pub.disconnect()
        await sub.disconnect()
    # after close the timed states leave the page; the sections stay
    text = reg.expose()
    assert checker.validate(text) == []
    assert 'maxmq_loop_seconds_total{state="idle"}' not in text
    assert 'maxmq_loop_seconds_total{state="read"}' in text
