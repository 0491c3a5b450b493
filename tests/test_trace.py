"""ADR-015 publish-path tracing suite: histogram bucket math + text
exposition, deterministic sampling (incl. the zero-allocations-when-off
contract), flight-recorder ring bounds and slow-threshold capture,
Chrome trace_event export, span nesting across the event loop / writer
thread / writer task / bridge boundaries on a real broker, the
per-stage error counter, and the Prometheus conformance checker the CI
lane runs (imported and exercised directly, so the tool is under test).
"""

import asyncio
import importlib.util
import json
import os
import time
import urllib.request

import pytest

from matching_helpers import EngineStub
from test_broker_system import connect, running_broker

from maxmq_tpu import faults
from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities, TCPListener
from maxmq_tpu.hooks import AllowHook
from maxmq_tpu.hooks.journal import WriteBehindStore
from maxmq_tpu.hooks.storage import MemoryStore, StorageHook
from maxmq_tpu.metrics import (Histogram, MetricsServer, Registry,
                               register_broker_metrics)
from maxmq_tpu.trace import (CRITICAL_STAGES, LOOP_STAGES, MAX_DRAIN_SPANS,
                             PipelineTracer, STAGES)


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()
    faults.REGISTRY.reset_clock()


async def poll(predicate, timeout: float = 5.0, what: str = ""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(0.02)
    raise AssertionError(f"condition not reached in {timeout}s: {what}")


def _checker():
    """Import scripts/check_metrics_exposition.py as a module (scripts/
    is not a package) so its validator is directly under test."""
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", "check_metrics_exposition.py")
    spec = importlib.util.spec_from_file_location("_expo_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- histogram units ---------------------------------------------------


def test_histogram_bucket_math():
    h = Histogram(buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.001, 0.05, 5.0):
        h.observe(v)
    # per-bucket: le=0.001 takes 0.0005 AND the exact-bound 0.001
    assert h.counts == [2, 0, 1, 1]
    assert h.count == 4
    assert h.sum == pytest.approx(5.0515)
    # quantiles interpolate within the owning bucket; the overflow
    # bucket clamps to the last finite bound
    assert 0.0 < h.quantile(0.25) <= 0.001
    assert 0.01 < h.quantile(0.74) <= 0.1
    assert h.quantile(0.99) == 0.1


def test_histogram_exposition_format():
    reg = Registry()
    h = Histogram(buckets=(0.001, 0.01))
    for v in (0.0005, 0.005, 2.0):
        h.observe(v)
    reg.histogram_func("t_seconds", "help.",
                       lambda: [({"stage": "x"}, h)])
    text = reg.expose()
    assert "# TYPE t_seconds histogram" in text
    assert 't_seconds_bucket{stage="x",le="0.001"} 1' in text
    assert 't_seconds_bucket{stage="x",le="0.01"} 2' in text
    assert 't_seconds_bucket{stage="x",le="+Inf"} 3' in text
    assert 't_seconds_count{stage="x"} 3' in text
    assert 't_seconds_sum{stage="x"} 2.0055' in text


# -- tracer units ------------------------------------------------------


def _finished_trace(tracer, e2e_ns=1_000_000, topic="t/x", qos=0):
    tr = tracer.sample(topic, qos, "c")
    assert tr is not None
    tr.span("admission", tr.start_ns, tr.start_ns + e2e_ns // 2)
    tr.span("fanout", tr.start_ns + e2e_ns // 2, tr.start_ns + e2e_ns)
    tracer.finish(tr, end_ns=tr.start_ns + e2e_ns)
    return tr


def test_sampling_stride_and_zero_alloc_counter():
    tracer = PipelineTracer(sample_n=2)
    got = [tracer.sample("t", 0, "c") for _ in range(10)]
    assert sum(1 for tr in got if tr is not None) == 5
    assert tracer.allocations == 5
    off = PipelineTracer(sample_n=0)
    assert all(off.sample("t", 0, "c") is None for _ in range(10))
    assert off.allocations == 0 and off.sampled == 0


def test_flight_recorder_ring_bounds():
    tracer = PipelineTracer(sample_n=1, ring=4)
    for _ in range(10):
        _finished_trace(tracer)
    assert tracer.ring_depth == 4
    ids = [e["id"] for e in tracer.report()["entries"]]
    assert ids == [7, 8, 9, 10]          # recency ring, oldest first


def test_slow_threshold_capture_and_slowest_list():
    tracer = PipelineTracer(sample_n=1, slow_ms=10.0, ring=8)
    _finished_trace(tracer, e2e_ns=5_000_000)       # 5ms: under
    assert tracer.ring_depth == 0 and tracer.slow_captured == 0
    _finished_trace(tracer, e2e_ns=20_000_000)      # 20ms: captured
    assert tracer.ring_depth == 1 and tracer.slow_captured == 1
    entry = tracer.report()["entries"][0]
    assert entry["slow"] is True
    assert entry["e2e_ms"] == pytest.approx(20.0)
    # the slowest-ever list survives ring churn and stays bounded
    for ms in range(11, 30):
        _finished_trace(tracer, e2e_ns=ms * 1_000_000)
    slowest = tracer.report()["slowest"]
    assert len(slowest) <= 8
    assert slowest[-1]["e2e_ms"] == pytest.approx(29.0)
    assert all(a["e2e_ms"] <= b["e2e_ms"]
               for a, b in zip(slowest, slowest[1:]))


def test_chrome_export_is_valid_trace_event_json():
    tracer = PipelineTracer(sample_n=1)
    _finished_trace(tracer, e2e_ns=3_000_000)
    blob = json.dumps(tracer.chrome_events())
    doc = json.loads(blob)
    events = doc["traceEvents"]
    # ADR 017: process_name metadata rows name the per-node tracks;
    # every span row stays a complete ('X') event
    spans = [e for e in events if e["ph"] != "M"]
    assert spans and all(e["ph"] == "X" for e in spans)
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)
    names = {e["name"] for e in spans}
    assert "admission" in names and "fanout" in names
    for e in spans:
        assert isinstance(e["ts"], int) and e["dur"] >= 1


def test_fault_registry_clock_drives_spans():
    """Deterministic-under-test contract: the tracer reads time through
    faults.REGISTRY.clock_ns, so a scripted clock scripts the spans."""
    t = [0]

    def scripted():
        t[0] += 1_000_000               # 1ms per observation
        return t[0]

    faults.REGISTRY.clock_ns = scripted
    tracer = PipelineTracer(sample_n=1)
    tr = tracer.sample("t", 0, "c")     # one clock read
    t0 = tracer.clock()
    tr.span("fanout", t0, tracer.clock())
    tracer.finish(tr)
    entry = tracer.report()["entries"][0]
    span = next(s for s in entry["spans"] if s["stage"] == "fanout")
    assert span["dur_us"] == 1000       # exactly one scripted tick
    assert entry["e2e_ms"] == pytest.approx(3.0)  # 3 ticks start->end


def test_stage_errors_counter_and_exposition():
    tracer = PipelineTracer()           # sampling off: errors still count
    tracer.note_error("drain", "queue_full", 3)
    tracer.note_error("bridge", "refused")
    assert tracer.stage_errors[("drain", "queue_full")] == 3

    class _B:                            # minimal broker facade
        pass

    b = _B()
    b.tracer = tracer
    reg = Registry()
    from maxmq_tpu.metrics import _register_trace_metrics
    _register_trace_metrics(reg, b)
    text = reg.expose()
    assert ('maxmq_broker_stage_errors_total'
            '{stage="drain",reason="queue_full"} 3') in text
    assert ('maxmq_broker_stage_errors_total'
            '{stage="bridge",reason="refused"} 1') in text
    # every pipeline stage exposes its histogram triplet even untouched;
    # the loop ledger's spans are rates a publish and have no histogram
    for stage in STAGES:
        assert ((f'maxmq_broker_publish_stage_seconds_count'
                 f'{{stage="{stage}"}} 0') in text) \
            == (stage not in LOOP_STAGES)


# -- e2e: spans on a real broker --------------------------------------


async def test_trie_path_spans_and_drain():
    async with running_broker(trace_sample_n=1) as broker:
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#")
        pub = await connect(broker, "p1")
        await pub.publish("t/x", b"payload")
        await sub.next_message(timeout=3)
        await poll(lambda: broker.tracer.ring_depth > 0, what="trace")
        entry = broker.tracer.report()["entries"][0]
        stages = {s["stage"] for s in entry["spans"]}
        assert {"decode", "admission", "match_device",
                "fanout"} <= stages
        assert entry["qos"] == 0 and entry["topic"] == "t/x"
        assert entry["client"] == "p1"
        # drain span lands after finish, from the writer task, and is
        # appended to the live flight-recorder entry
        await poll(lambda: entry["drains"], what="drain span")
        assert entry["drains"][0]["client"] == "s1"
        # zero stage errors on a healthy publish
        assert broker.tracer.stage_errors == {}
        await pub.disconnect()
        await sub.disconnect()


async def test_zero_allocations_when_off():
    async with running_broker() as broker:      # default: tracing off
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#")
        pub = await connect(broker, "p1")
        for i in range(10):
            await pub.publish("t/x", b"m", qos=1)
        await sub.next_message(timeout=3)
        assert broker.tracer.allocations == 0
        assert broker.tracer.sampled == 0
        assert broker.tracer.ring_depth == 0
        await pub.disconnect()
        await sub.disconnect()


async def test_durable_barrier_span_crosses_writer_thread():
    """storage_sync=always: the barrier span opens on the loop and is
    closed by an ack released from the storage writer thread; a slow
    group commit (hang fault in the WRITER thread) must show up as
    barrier time, and the critical-path spans must sum to ~e2e (the
    acceptance bar: within 10%)."""
    store = WriteBehindStore(MemoryStore(), policy="always")
    b = Broker(BrokerOptions(capabilities=Capabilities(
        sys_topic_interval=0, trace_sample_n=1, trace_slow_ms=20.0)))
    b.add_hook(AllowHook())
    b.add_hook(StorageHook(store))
    lst = b.add_listener(TCPListener("t", "127.0.0.1:0"))
    await b.serve()
    b.test_port = lst._server.sockets[0].getsockname()[1]
    try:
        sub = await connect(b, "s1")
        await sub.subscribe(("t/#", 1))
        pub = await connect(b, "p1")
        # fast publish first: under the 20ms slow threshold -> NOT
        # flight-recorded (but histograms still fed)
        await pub.publish("t/fast", b"m", qos=1, timeout=5)
        await poll(lambda: b.tracer.sampled >= 1, what="sampled")
        assert b.tracer.ring_depth == 0
        # slow publish: the commit covering its barrier hangs 60ms in
        # the writer thread
        faults.arm(faults.STORAGE_COMMIT, "hang", count=1, delay_s=0.06)
        t0 = time.perf_counter()
        await pub.publish("t/slow", b"m", qos=1, timeout=10)
        measured_ms = (time.perf_counter() - t0) * 1e3
        await poll(lambda: b.tracer.ring_depth > 0, what="slow capture")
        entry = b.tracer.report()["entries"][0]
        assert entry["slow"] is True and entry["topic"] == "t/slow"
        spans = {s["stage"]: s for s in entry["spans"]}
        assert "barrier" in spans and "ack" in spans
        assert spans["barrier"]["dur_us"] >= 50_000
        # spans are the decomposition of the measured e2e: within 10%
        assert entry["critical_sum_ms"] >= 0.9 * entry["e2e_ms"]
        assert entry["e2e_ms"] <= measured_ms * 1.1
        assert b.storage_barrier_waits >= 1
        # journal_commit histogram fed from the writer thread
        assert b.tracer.stage_hist["journal_commit"].count >= 1
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await b.close()


async def test_matcher_pipeline_split_spans_through_supervisor():
    """Matcher mode: the batcher stamps dispatch/done marks, the
    ADR-011 supervisor forwards them, and the trace splits the matcher
    leg into match_queue + match_device (+ pipeline_wait)."""
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.supervisor import SupervisedMatcher

    class _TrieEngine(EngineStub):
        def __init__(self, index):
            self.index = index

        def subscribers_batch(self, topics):
            return [self.index.subscribers(t) for t in topics]

    async with running_broker(trace_sample_n=1) as broker:
        batcher = MicroBatcher(_TrieEngine(broker.topics),
                               cpu_bypass=False, window_us=1000)
        batcher.tracer = broker.tracer
        broker.attach_matcher(SupervisedMatcher(
            batcher, index=broker.topics, deadline_ms=2000))
        try:
            sub = await connect(broker, "s1")
            await sub.subscribe("m/#")
            pub = await connect(broker, "p1")
            await pub.publish("m/x", b"payload")
            await sub.next_message(timeout=3)
            await poll(lambda: broker.tracer.ring_depth > 0,
                       what="matcher trace")
            entry = broker.tracer.report()["entries"][0]
            stages = {s["stage"] for s in entry["spans"]}
            assert "match_queue" in stages and "match_device" in stages
            assert entry["degraded"] == ""      # healthy supervisor
            await pub.disconnect()
            await sub.disconnect()
        finally:
            await batcher.close()


async def test_bridge_span_and_link_down_stage_error():
    """Cluster attached: the bridge span wraps the route consult +
    forward enqueue, and a forward whose target link is down lands on
    the stage-error counter as (bridge, link_down)."""
    from maxmq_tpu.cluster import ClusterManager, PeerSpec

    async with running_broker(trace_sample_n=1) as broker:
        mgr = ClusterManager(
            broker, "A", [PeerSpec("B", "127.0.0.1", 1)])
        broker.attach_cluster(mgr)      # attached post-serve: links idle
        # B advertises a route so maybe_forward targets its dead link
        mgr.routes.apply_snapshot("B", 1, 1, {"t/#"})
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#")
        pub = await connect(broker, "p1")
        await pub.publish("t/x", b"payload")
        await sub.next_message(timeout=3)
        await poll(lambda: broker.tracer.ring_depth > 0, what="trace")
        entry = broker.tracer.report()["entries"][0]
        assert "bridge" in {s["stage"] for s in entry["spans"]}
        assert broker.tracer.stage_errors.get(
            ("bridge", "link_down"), 0) >= 1
        assert mgr.forwards_skipped_down >= 1
        await pub.disconnect()
        await sub.disconnect()


async def test_drain_stage_error_from_write_path_drop():
    """The ADR-012 drops_by_reason ledger now surfaces per-stage: a
    queue-refused delivery counts under stage=drain with its reason."""
    async with running_broker(maximum_client_writes_pending=1) as broker:
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#")
        # stall the subscriber's writer so its 1-slot queue wedges
        faults.arm(f"{faults.CLIENT_WRITE}#s1", "hang",
                   count=-1, delay_s=30.0)
        pub = await connect(broker, "p1")
        for i in range(20):
            await pub.publish("t/x", b"m" * 64)
        await poll(lambda: any(s == "drain" for (s, _r)
                               in broker.tracer.stage_errors),
                   what="drain stage error")
        reasons = {r for (s, r) in broker.tracer.stage_errors
                   if s == "drain"}
        assert "queue_full" in reasons
        await pub.disconnect()


async def test_sys_trace_subtree_and_metrics_endpoints():
    async with running_broker(trace_sample_n=1) as broker:
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#")
        pub = await connect(broker, "p1")
        await pub.publish("t/x", b"m", qos=1)
        await sub.next_message(timeout=3)
        await poll(lambda: broker.tracer.ring_depth > 0, what="trace")
        broker.publish_sys_topics()
        assert broker.topics.retained_get(
            "$SYS/broker/trace/sampled") is not None
        assert broker.topics.retained_get(
            "$SYS/broker/trace/ring_depth") is not None
        # sampling off -> the next tick CLEARS the retained subtree
        # (stale values must not masquerade as live ones)
        broker.tracer.sample_n = 0
        broker.publish_sys_topics()
        assert broker.topics.retained_get(
            "$SYS/broker/trace/sampled") is None
        assert broker.topics.retained_get(
            "$SYS/broker/trace/ring_depth") is None
        broker.tracer.sample_n = 1

        reg = Registry()
        register_broker_metrics(reg, broker)
        srv = MetricsServer("127.0.0.1:0", reg, tracer=broker.tracer)
        srv.start()
        try:
            def get(path):
                url = f"http://127.0.0.1:{srv.bound_port}{path}"
                with urllib.request.urlopen(url, timeout=5) as r:
                    return r.read().decode()

            loop = asyncio.get_running_loop()
            traces = json.loads(
                await loop.run_in_executor(None, get, "/traces"))
            assert traces["sample_n"] == 1 and traces["entries"]
            chrome = json.loads(
                await loop.run_in_executor(None, get, "/traces/chrome"))
            assert chrome["traceEvents"]
            page = await loop.run_in_executor(None, get, "/metrics")
            assert "maxmq_broker_publish_e2e_seconds_bucket" in page
        finally:
            srv.stop()
        await pub.disconnect()
        await sub.disconnect()


# -- the conformance checker itself ------------------------------------


def test_exposition_checker_passes_on_real_registry():
    checker = _checker()
    b = Broker(BrokerOptions(capabilities=Capabilities(
        sys_topic_interval=0, trace_sample_n=1)))
    b.add_hook(StorageHook(WriteBehindStore(MemoryStore())))
    b.tracer.observe("fanout", 0.003)
    tr = b.tracer.sample("t", 0, 'cli"ent\\x')
    b.tracer.finish(tr, end_ns=tr.start_ns + 1000)
    reg = Registry()
    register_broker_metrics(reg, b)
    errors = checker.validate(reg.expose())
    assert errors == []
    b.hooks.stop_all()


def test_exposition_checker_catches_violations():
    checker = _checker()
    bad = "\n".join((
        "# TYPE h_seconds histogram",
        'h_seconds_bucket{le="0.1"} 5',
        'h_seconds_bucket{le="1"} 3',        # non-monotonic
        'h_seconds_bucket{le="+Inf"} 5',
        "h_seconds_sum 1.0",
        "h_seconds_count 9",                 # != +Inf bucket
        "no_type_metric 1",                  # no TYPE declared
        'lbl{bad name="x"} 1',               # malformed label
        "dup 1",
    ))
    errors = checker.validate("# TYPE dup counter\n# TYPE lbl gauge\n"
                              "# TYPE no_type_metric_ignored gauge\n"
                              + bad + "\ndup 1\n")
    text = "\n".join(errors)
    assert "non-monotonic" in text
    assert "_count" in text
    assert "no TYPE declared" in text
    assert "malformed" in text or "unparseable" in text
    assert "duplicate series" in text


async def test_drain_watchers_settle_only_when_their_flush_lands():
    """A watcher registered while a flush is in flight must NOT be
    settled by that flush (its packet is still queued) — settling is
    gated on the writer having dequeued past the watcher's enqueue
    seq, so slow-consumer drain latency is reported, not hidden."""
    async with running_broker(trace_sample_n=1) as broker:
        sub = await connect(broker, "s1")
        await sub.subscribe("t/#")
        client = broker.clients.get("s1")
        tracer = broker.tracer
        tr1 = tracer.sample("t/a", 0, "p")
        tr2 = tracer.sample("t/b", 0, "p")
        # watcher 1 at the current dequeue frontier, watcher 2 beyond
        flushed_now = client.outbound.removed
        client._drain_traces = [(tr1, tracer.clock(), flushed_now),
                                (tr2, tracer.clock(), flushed_now + 5)]
        client._settle_drain_traces(flushed_now)
        assert [seq for _t, _n, seq in client._drain_traces] == \
            [flushed_now + 5]                   # tr2 kept pending
        assert len(tr1.drains) == 1 and tr2.drains == []
        await sub.disconnect()


def test_drain_span_cap():
    tracer = PipelineTracer(sample_n=1)
    tr = tracer.sample("t", 0, "c")
    for i in range(20):
        tracer.drain_span(tr, f"c{i}", 0, 1000)
    # the SERVER-side registration caps at MAX_DRAIN_SPANS; the tracer
    # records whatever was registered — the cap constant is the contract
    assert MAX_DRAIN_SPANS < 20
    assert tracer.stage_hist["drain"].count == 20
    assert CRITICAL_STAGES.isdisjoint({"drain", "journal_commit"})


# -- the matcher leg: who answered, the batch's phases, the loop --------


def _unserved_broker(**caps):
    caps.setdefault("trace_sample_n", 1)
    return Broker(BrokerOptions(capabilities=Capabilities(
        sys_topic_interval=0, **caps)))


def _sampled_publish(broker, topic):
    """What process_publish does to open a sampled publish's trace,
    without a socket: needs a running loop (the loop_lag stamp)."""
    from types import SimpleNamespace
    packet = SimpleNamespace(topic=topic, fixed=SimpleNamespace(qos=0))
    broker._trace_begin(SimpleNamespace(id="p1"), packet)
    return packet


async def _publish_through(broker, matcher, topics, before_spans=None):
    """_enqueue_publish and the in-order consumer's tracing steps for a
    run of publishes over ``matcher``; returns their ring entries."""
    tracer, items = broker.tracer, []
    for topic in topics:
        packet = _sampled_publish(broker, topic)
        packet._trace.t_match = tracer.clock()
        items.append((matcher.enqueue(topic), packet))
    for fut, packet in items:
        await fut
        if before_spans is not None:
            await before_spans()
        broker._trace_match_spans(fut, packet)
        tracer.finish(packet._trace)
    return [packet._trace.entry for _f, packet in items]


def _spans(entry, stage):
    return [s for s in entry["spans"] if s["stage"] == stage]


class _Clock:
    """A clock the test moves by hand, behind the fault registry."""

    def __init__(self):
        self.ns = 1_000_000_000
        faults.REGISTRY.clock_ns = lambda: self.ns

    def advance(self, ms):
        self.ns += int(ms * 1e6)


async def test_cache_hit_ends_match_device_at_the_answer():
    """A topic-cache hit is answered inside enqueue: match_device ends
    there, and the 5 ms the in-order consumer took to reach the publish
    are pipeline_wait (they used to be booked as match_device)."""
    from maxmq_tpu.matching.batcher import MicroBatcher
    from test_batcher import FakeEngine

    clock = _Clock()
    broker = _unserved_broker()
    batcher = MicroBatcher(FakeEngine(), window_us=0, cpu_bypass=False)
    batcher.tracer = broker.tracer
    try:
        await batcher.subscribers_async("hot/a")        # fills the cache
        (entry,) = await _publish_through(
            broker, batcher, ["hot/a"],
            before_spans=lambda: asyncio.sleep(0, clock.advance(5)))
        (dev,) = _spans(entry, "match_device")
        assert dev["via"] == "cache" and dev["dur_us"] == 0
        assert "batch" not in dev and dev["parent"] == ""
        (wait,) = _spans(entry, "pipeline_wait")
        assert wait["dur_us"] == 5000
        assert not _spans(entry, "match_queue")
        assert batcher.cache_hits == 1
    finally:
        await batcher.close()


async def test_supervisor_trie_answer_ends_match_device_at_the_answer():
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.supervisor import (BREAKER_OPEN,
                                               SupervisedMatcher)
    from test_batcher import FakeEngine

    clock = _Clock()
    broker = _unserved_broker()
    batcher = MicroBatcher(FakeEngine(), window_us=0, cpu_bypass=False)
    batcher.tracer = broker.tracer
    sup = SupervisedMatcher(batcher, index=broker.topics)
    sup._state, sup._open_until = BREAKER_OPEN, float("inf")
    try:
        (entry,) = await _publish_through(
            broker, sup, ["t/x"],
            before_spans=lambda: asyncio.sleep(0, clock.advance(7)))
        (dev,) = _spans(entry, "match_device")
        assert dev["via"] == "fallback" and dev["dur_us"] == 0
        (wait,) = _spans(entry, "pipeline_wait")
        assert wait["dur_us"] == 7000
        assert sup.breaker_fallbacks == 1 and batcher.batches == 0
    finally:
        await batcher.close()


async def test_bypassed_batch_children_share_one_batch_id():
    from test_batcher import _traced_sig_batcher

    broker = _unserved_broker()
    batcher = _traced_sig_batcher(broker.tracer)
    try:
        batcher._device_rtt, batcher._rtt_samples = 10.0, 2
        entries = await _publish_through(
            broker, batcher, [f"tr/{i}/x" for i in range(12)])
        ids = set()
        for entry in entries:
            (dev,) = _spans(entry, "match_device")
            assert dev["via"] == "host" and dev["parent"] == ""
            ids.add(dev["batch"])
            kids = [s for s in entry["spans"] if s["parent"]]
            assert {s["stage"] for s in kids} == {
                "match_host", "match_prep", "match_probe", "match_decode"}
            assert all(s["parent"] == "match_device"
                       and s["batch"] == dev["batch"] for s in kids)
            assert all(s["parent"] == "" for s in entry["spans"]
                       if s not in kids)
            # children are not summed twice
            critical = sum(s["dur_us"] for s in entry["spans"]
                           if s["stage"] in CRITICAL_STAGES)
            assert entry["critical_sum_ms"] == pytest.approx(
                critical / 1e3, abs=0.02)
            assert not CRITICAL_STAGES & {s["stage"] for s in kids}
            (host,) = _spans(entry, "match_host")
            assert host["dur_us"] <= dev["dur_us"] + 1
        assert len(ids) == 1
        # every sampled publish of the batch fed the stage histogram
        assert broker.tracer.stage_hist["match_host"].count == 12
        assert broker.tracer.batch_hist["match_host"].count == 1
    finally:
        await batcher.close()


@pytest.mark.parametrize("probe_first", [False, True])
async def test_shadow_probe_phases_reach_the_duplicated_batchs_entries(
        probe_first):
    """The probe ends after the publishes finished (its spans are
    appended to the live ring entries, like drains) or before the
    consumer reached them (copied with the batch's own phases)."""
    from test_batcher import _traced_sig_batcher

    broker = _unserved_broker()
    batcher = _traced_sig_batcher(broker.tracer)

    async def wait_for_probe():
        if probe_first:
            await batcher._probe_task

    try:
        batcher._device_rtt, batcher._rtt_samples = 10.0, 2
        batcher._since_probe = batcher.BYPASS_PROBE_EVERY - 1
        entries = await _publish_through(
            broker, batcher, [f"tr/{i}/x" for i in range(6)],
            before_spans=wait_for_probe)
        await batcher._probe_task
        shown = broker.tracer.report()["batches"]
        assert [b.get("of") for b in shown] == [None, shown[0]["id"]]
        for entry in entries:
            (dev,) = _spans(entry, "match_device")
            assert dev["batch"] == shown[0]["id"]
            late = [s for s in entry["spans"] if s.get("shadow")]
            assert {s["stage"] for s in late} >= {
                "match_dispatch", "match_fetch", "device_rtt", "match_hop"}
            assert all(s["batch"] == shown[1]["id"]
                       and s["parent"] == "match_device" for s in late)
            assert len(_spans(entry, "device_rtt")) == 1
            (host,) = _spans(entry, "match_host")
            assert "shadow" not in host
        hist = broker.tracer.stage_hist
        assert hist["device_rtt"].count == 6 == hist["match_hop"].count
        assert batcher.device_round_trip > 0
    finally:
        await batcher.close()


async def test_loop_lag_is_how_long_a_ready_callback_waited():
    clock = _Clock()
    broker = _unserved_broker()
    loop = asyncio.get_running_loop()
    # a callback ahead in the ready queue holds the loop for 50 ms
    loop.call_soon(clock.advance, 50)
    tr = _sampled_publish(broker, "t/x")._trace
    await asyncio.sleep(0)
    assert [(s, dur) for s, _t0, dur in tr.spans] == \
        [("loop_lag", 50_000_000)]
    broker.tracer.finish(tr)
    # a publish that finished first gets it on its live ring entry
    loop.call_soon(clock.advance, 20)
    tr = _sampled_publish(broker, "t/y")._trace
    broker.tracer.finish(tr)
    assert not _spans(tr.entry, "loop_lag")
    await asyncio.sleep(0)
    (lag,) = _spans(tr.entry, "loop_lag")
    assert lag["dur_us"] == 20_000 and lag["parent"] == ""
    assert broker.tracer.stage_hist["loop_lag"].count == 2
    assert "loop_lag" in STAGES and "loop_lag" not in CRITICAL_STAGES


async def test_new_sites_cost_nothing_with_sampling_off(monkeypatch):
    """ADR 015's cost contract at the sites ISSUE 25 added: with
    sample_n = 0 no TraceAnnotation is built, no call_soon stamp is
    scheduled, no batch record opened, no mark put on a future."""
    from maxmq_tpu import trace
    from maxmq_tpu.matching.supervisor import SupervisedMatcher
    from test_batcher import _traced_sig_batcher

    built = []

    class Annotation:
        def __init__(self, name, **stats):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_annotation", Annotation)
    async with running_broker() as broker:          # tracing off
        lags = []
        broker._trace_loop_lag = lambda *a: lags.append(a)
        batcher = _traced_sig_batcher(broker.tracer)
        batcher.engine.index = broker.topics
        batcher._device_rtt, batcher._rtt_samples = 10.0, 2
        broker.attach_matcher(SupervisedMatcher(
            batcher, index=broker.topics, deadline_ms=5000))
        try:
            sub = await connect(broker, "s1")
            await sub.subscribe("t/#")
            batcher.engine.refresh()
            pub = await connect(broker, "p1")
            for i in range(10):
                await pub.publish(f"t/{i % 3}", b"m", qos=1)
            await sub.next_message(timeout=3)
            assert batcher.batches >= 1
            assert built == [] and lags == []
            assert broker.tracer.allocations == 0
            assert broker.tracer.report()["batches"] == []
            # the same traffic with sampling on builds them
            broker.tracer.sample_n = 1
            await pub.publish("t/9", b"m", qos=1)
            await sub.next_message(timeout=3)
            await poll(lambda: {"maxmq.read", "maxmq.deliver",
                                "maxmq.settle", "maxmq.flush"}
                       <= set(built), what="annotations")
            await poll(lambda: lags, what="loop_lag stamp")
            await pub.disconnect()
            await sub.disconnect()
        finally:
            await batcher.close()


async def test_annotated_closes_its_span_across_a_suspension(monkeypatch):
    """A host span is scoped to a thread: annotated() leaves it open
    only while the coroutine runs, never while it waits."""
    from maxmq_tpu import trace

    log = []

    class Annotation:
        def __init__(self, name, **stats):
            self.name = name

        def __enter__(self):
            log.append("open")

        def __exit__(self, *exc):
            log.append("close")

    monkeypatch.setattr(trace, "_annotation", Annotation)
    gate = asyncio.get_running_loop().create_future()

    async def work():
        log.append("a")
        got = await gate
        log.append(got)
        return "done"

    tracer = PipelineTracer(sample_n=1)
    task = asyncio.ensure_future(trace.annotated(tracer, "read", work()))
    await asyncio.sleep(0)
    assert log == ["open", "a", "close"]
    gate.set_result("b")
    assert await task == "done"
    assert log == ["open", "a", "close", "open", "b", "close"]
    # an exception thrown in at the suspension reaches the coroutine

    async def waits():
        try:
            await asyncio.sleep(30)
        except asyncio.CancelledError:
            log.append("cancelled")
            raise

    del log[:]
    task = asyncio.ensure_future(trace.annotated(tracer, "read", waits()))
    await asyncio.sleep(0)
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    assert log == ["open", "close", "open", "cancelled", "close"]


def _layer_files(reader):
    root = os.path.join(os.path.dirname(__file__), os.pardir, "perfbench",
                        "layers")
    out = {}
    for name in sorted(os.listdir(root)):
        with open(os.path.join(root, name)) as fh:
            layer = json.load(fh)
        if layer["reader"] == reader:
            out[name[:-len(".json")]] = layer
    return out


def test_every_ring_stage_the_benchmark_reads_is_a_stage():
    """A renamed stage must fail here, not go absent from the ledger."""
    layers = _layer_files("ring_stage_median")
    assert len(layers) >= 13
    for name, layer in layers.items():
        assert layer["args"]["stage"] in STAGES, name


ISSUE_25_METRICS = {
    "loop_lag_ms.flood": "loop_lag",
    "engine_host_answer_ms.flood": "match_host",
    "engine_prep_us.flood": "match_prep",
    "engine_probe_us.flood": "match_probe",
    "engine_decode_us.flood": "match_decode",
    "device_rtt_us.flood": "device_rtt",
    "settle_hop_ms.flood": "match_hop",
}


async def test_the_benchmarks_reader_reads_each_new_metric_from_the_ring():
    """The flood's regime in small: bypassed batches and one shadow
    probe. perfbench's own reader then finds every metric ISSUE 25
    added in the entries the tracer made, through the metric's file."""
    import sys
    from test_batcher import _traced_sig_batcher
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "perfbench"))
    try:
        import readers
    finally:
        sys.path.pop(0)

    broker = _unserved_broker()
    batcher = _traced_sig_batcher(broker.tracer)
    try:
        batcher._device_rtt, batcher._rtt_samples = 10.0, 2
        batcher._since_probe = batcher.BYPASS_PROBE_EVERY - 1
        await _publish_through(broker, batcher,
                               [f"tr/{i}/x" for i in range(8)])
        await batcher._probe_task
        await asyncio.sleep(0)
    finally:
        await batcher.close()
    run = {"ring": broker.tracer.report()["entries"]}
    layers = _layer_files("ring_stage_median")
    for name, stage in ISSUE_25_METRICS.items():
        layer = layers[name]
        assert layer["args"]["stage"] == stage
        value = readers.ring_stage_median(run, **layer["args"])
        assert value is not None and value >= 0, name
    # a ring of the parent's shape (no such span) reads as absent
    old = {"ring": [{"spans": [{"stage": "match_device", "dur_us": 9}],
                     "drains": []}]}
    assert readers.ring_stage_median(old, stage="match_host") is None
