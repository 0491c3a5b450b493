"""Tests for the micro-batching matcher front end (SURVEY §7 stage 4: the
publish micro-batch queue in front of the device matcher)."""

from __future__ import annotations

import asyncio

import pytest

from maxmq_tpu.matching.batcher import MicroBatcher
from maxmq_tpu.matching.trie import TopicIndex
from maxmq_tpu.protocol.packets import Subscription

from matching_helpers import EngineStub


class FakeEngine(EngineStub):
    """Records the batch shapes the batcher dispatches."""

    def __init__(self) -> None:
        self.index = TopicIndex()
        self.calls: list[list[str]] = []

    def subscribers_batch(self, topics):
        self.calls.append(list(topics))
        return [f"result:{t}" for t in topics]


async def test_concurrent_requests_coalesce():
    eng = FakeEngine()
    batcher = MicroBatcher(eng, window_us=2000, max_batch=64, cpu_bypass=False)
    try:
        results = await asyncio.gather(
            *[batcher.subscribers_async(f"t/{i}") for i in range(16)])
        assert results == [f"result:t/{i}" for i in range(16)]
        # all 16 concurrent requests land in ONE device dispatch
        assert len(eng.calls) == 1
        assert len(eng.calls[0]) == 16
        assert batcher.batches == 1
        assert batcher.largest_batch == 16
    finally:
        await batcher.close()


async def test_max_batch_splits():
    eng = FakeEngine()
    batcher = MicroBatcher(eng, window_us=1000, max_batch=4, cpu_bypass=False)
    try:
        results = await asyncio.gather(
            *[batcher.subscribers_async(f"t/{i}") for i in range(10)])
        assert results == [f"result:t/{i}" for i in range(10)]
        assert all(len(c) <= 4 for c in eng.calls)
        assert sum(len(c) for c in eng.calls) == 10
    finally:
        await batcher.close()


async def test_single_request_low_latency():
    eng = FakeEngine()
    batcher = MicroBatcher(eng, window_us=100, max_batch=64, cpu_bypass=False)
    try:
        out = await asyncio.wait_for(batcher.subscribers_async("a/b"),
                                     timeout=1)
        assert out == "result:a/b"
    finally:
        await batcher.close()


async def test_engine_error_propagates():
    class Boom(FakeEngine):
        def subscribers_batch(self, topics):
            raise RuntimeError("device fell over")

    batcher = MicroBatcher(Boom(), window_us=100)
    try:
        with pytest.raises(RuntimeError):
            await batcher.subscribers_async("a/b")
    finally:
        await batcher.close()


async def test_batched_sig_engine_parity():
    """End to end with the real device matcher: batched answers equal
    the exact CPU trie."""
    from maxmq_tpu.matching.sig import SigEngine

    index = TopicIndex()
    for i, f in enumerate(["a/+", "a/b", "a/#", "x/y", "+/y", "$sys/#"]):
        index.subscribe(f"cl-{i}", Subscription(filter=f, qos=1))
    engine = SigEngine(index, max_levels=6)
    engine.route_small = False      # the device, not the ADR-008 router
    batcher = MicroBatcher(engine, window_us=500, max_batch=32)
    try:
        topics = ["a/b", "a/c", "x/y", "q/y", "$sys/health", "nope"] * 3
        got = await asyncio.gather(
            *[batcher.subscribers_async(t) for t in topics])
        for topic, s in zip(topics, got):
            want = index.subscribers(topic)
            assert set(s.subscriptions) == set(want.subscriptions), topic
        assert engine.matches == len(topics) and not batcher.bypasses
    finally:
        await batcher.close()


def test_batcher_delegates_sync_surface():
    eng = FakeEngine()
    batcher = MicroBatcher(eng, cpu_bypass=False)
    assert batcher.subscribers("a") == "result:a"
    assert batcher.refresh() is False
    assert batcher.index is eng.index


class SplitEngine(EngineStub):
    """Dispatch/collect split with a slow collect: lets the pipelining
    test observe multiple batches in flight."""

    def __init__(self, collect_s: float = 0.05) -> None:
        import threading
        import time as _time

        self.index = TopicIndex()
        self.collect_s = collect_s
        self.concurrent = 0
        self.max_concurrent = 0
        self._lk = threading.Lock()
        self._time = _time

    def dispatch_fixed(self, topics):
        return ("ctx", list(topics))

    def collect_fixed(self, topics, ctx):
        with self._lk:
            self.concurrent += 1
            self.max_concurrent = max(self.max_concurrent,
                                      self.concurrent)
        self._time.sleep(self.collect_s)   # the "link round trip"
        with self._lk:
            self.concurrent -= 1
        assert ctx == ("ctx", list(topics))
        return [f"r:{t}" for t in topics]

    def subscribers_batch(self, topics):
        return self.collect_fixed(topics, self.dispatch_fixed(topics))

    def _routes_to_trie(self):
        return False


async def test_pipelined_batches_overlap():
    # with the dispatch/collect split, queued batches must not serialize
    # behind the round trip of the batch ahead of them
    eng = SplitEngine()
    batcher = MicroBatcher(eng, window_us=0, max_batch=2,
                           pipeline_depth=3, cpu_bypass=False)
    try:
        results = await asyncio.gather(
            *[batcher.subscribers_async(f"p/{i}") for i in range(12)])
        assert sorted(results) == sorted(f"r:p/{i}" for i in range(12))
        assert eng.max_concurrent >= 2, eng.max_concurrent
    finally:
        await batcher.close()


async def test_pipeline_depth_one_still_serializes():
    eng = SplitEngine(collect_s=0.01)
    batcher = MicroBatcher(eng, window_us=0, max_batch=2,
                           pipeline_depth=1, cpu_bypass=False)
    try:
        results = await asyncio.gather(
            *[batcher.subscribers_async(f"q/{i}") for i in range(8)])
        assert sorted(results) == sorted(f"r:q/{i}" for i in range(8))
        assert eng.max_concurrent == 1
    finally:
        await batcher.close()


async def test_pipelined_collect_failure_fails_only_its_batch():
    class Flaky(SplitEngine):
        def collect_fixed(self, topics, ctx):
            if any(t.endswith("boom") for t in topics):
                raise RuntimeError("device fell over")
            return super().collect_fixed(topics, ctx)

    eng = Flaky(collect_s=0.005)
    batcher = MicroBatcher(eng, window_us=0, max_batch=1, cpu_bypass=False,
                           pipeline_depth=2)
    try:
        ok_futs = [batcher.subscribers_async(f"z/{i}") for i in range(3)]
        bad = batcher.subscribers_async("z/boom")
        ok = await asyncio.gather(*ok_futs)
        assert sorted(ok) == sorted(f"r:z/{i}" for i in range(3))
        with pytest.raises(RuntimeError):
            await bad
    finally:
        await batcher.close()


async def test_pipelined_dispatch_refusal_falls_back_to_whole_batch():
    # a corpus the device path declines (sig.py: > MAX_GROUPS) raises
    # from dispatch_fixed; the batcher must degrade to the whole-batch
    # function (which carries the CPU-trie fallback), never fail callers
    class TrieOnly(SplitEngine):
        def dispatch_fixed(self, topics):
            raise RuntimeError("device matching disabled for this corpus")

        def subscribers_batch(self, topics):
            return [f"trie:{t}" for t in topics]

    eng = TrieOnly()
    batcher = MicroBatcher(eng, window_us=0, max_batch=4, cpu_bypass=False,
                           pipeline_depth=3)
    try:
        results = await asyncio.gather(
            *[batcher.subscribers_async(f"f/{i}") for i in range(6)])
        assert sorted(results) == sorted(f"trie:f/{i}" for i in range(6))
    finally:
        await batcher.close()


async def test_enqueue_cache_hits_and_version_invalidation():
    """Matcher-mode match cache: repeated topics resolve without a
    device round trip; any subscription change (sub_version bump)
    invalidates (ADR 006 observability: cache_hits)."""
    from maxmq_tpu.protocol import Subscription

    class Counting(SplitEngine):
        def __init__(self):
            super().__init__(collect_s=0.0)
            self.dispatched = 0

        def dispatch_fixed(self, topics):
            self.dispatched += len(topics)
            return ("ctx", list(topics))

    eng = Counting()
    batcher = MicroBatcher(eng, window_us=0, max_batch=8, cpu_bypass=False)
    try:
        r1 = await batcher.subscribers_async("hot/a")
        r2 = await batcher.subscribers_async("hot/a")   # cache hit
        assert r1 == r2 == "r:hot/a"
        assert batcher.cache_hits == 1
        assert eng.dispatched == 1
        # a subscription change must invalidate the cached result
        eng.index.subscribe("c1", Subscription(filter="hot/a"))
        await batcher.subscribers_async("hot/a")
        assert eng.dispatched == 2
    finally:
        await batcher.close()


async def test_trie_bypass_still_notices_stale_tables():
    """A batch the bypass serves from the trie never reaches the engine,
    where staleness is otherwise noticed: the batcher must kick the
    background recompile itself, or a lightly loaded broker serves from
    stale tables (overlay and all) until heavy traffic arrives."""
    from maxmq_tpu.matching.sig import SigEngine

    index = TopicIndex()
    for i in range(300):
        index.subscribe(f"cl-{i}", Subscription(filter=f"by/{i}/+", qos=1))
    eng = SigEngine(index)
    batcher = MicroBatcher(eng, window_us=0, max_batch=64)
    try:
        batcher._device_rtt, batcher._rtt_samples = 0.05, 2
        batcher._trie_cost = 1e-9           # the trie wins every batch
        index.subscribe("late", Subscription(filter="by/9/+", qos=0))
        assert eng._stale()
        r = await batcher.subscribers_async("by/9/x")
        assert batcher.bypasses == 1 and eng.host_matches == 0
        assert "late" in r.subscriptions
        eng.close()
        assert not eng._stale() and eng.bg_refresh_errors == 0
    finally:
        await batcher.close()


async def test_rtt_sample_during_background_compile_is_discarded():
    """A round trip timed while a table rotation shares the interpreter
    measures the rotation; folded into the estimate it talks the bypass
    into winning every batch long after the rotation has ended."""
    batcher = MicroBatcher(FakeEngine(), window_us=0)
    batcher._note_rtt(9.9)                  # first: carries the compile
    batcher._note_rtt(0.002)
    assert batcher.device_rtt == 0.002
    batcher.engine.compiling = True
    batcher._note_rtt(3.1)
    assert batcher.device_rtt == 0.002
    batcher.engine.compiling = False
    batcher._note_rtt(0.004)
    assert 0.002 < batcher.device_rtt < 0.004


async def test_adaptive_cpu_bypass_serves_small_batches():
    """VERDICT r04 #2: with a measured device RTT on record, a small
    batch is served inline from the CPU trie (trie-class latency) with
    exact results; the probe cadence still sends periodic batches to
    the device so the RTT estimate cannot go stale."""
    from maxmq_tpu.matching.sig import SigEngine

    index = TopicIndex()
    for i in range(200):
        index.subscribe(f"cl-{i}", Subscription(filter=f"by/{i}/+", qos=1))
    eng = SigEngine(index)
    eng.route_small = False      # this test exercises the device path
    batcher = MicroBatcher(eng, window_us=0, max_batch=64)
    try:
        # no RTT sample yet: everything goes to the device path
        r = await batcher.subscribers_async("by/7/x")
        assert "cl-7" in (r.to_set() if hasattr(r, "to_set") else r).subscriptions
        assert batcher.bypasses == 0
        # seed a slow measured round trip (a remote-link regime)
        batcher._device_rtt = 0.05
        batcher._rtt_samples = 2
        r = await batcher.subscribers_async("by/9/x")
        assert batcher.bypasses >= 1, "small batch should take the bypass"
        assert "cl-9" in r.subscriptions          # trie-shaped result
        # correctness across a subscription change mid-bypass-regime
        index.subscribe("late", Subscription(filter="by/9/+", qos=0))
        for _ in range(3):
            r = await batcher.subscribers_async("by/9/x")
        assert "late" in r.subscriptions
        # probe cadence: at the threshold the NEXT bypassed batch spawns
        # a background shadow probe (callers never wait on it) that
        # refreshes the RTT estimate
        batcher._since_probe = batcher.BYPASS_PROBE_EVERY
        assert batcher._should_bypass(1)   # callers still bypass
        await batcher.subscribers_async("by/11/x")
        assert batcher._probe_task is not None
        await batcher._probe_task
        assert batcher._since_probe <= 1
        # ... and so does an estimate that has stood BYPASS_PROBE_SECONDS,
        # however few batches came by: a broker whose loop makes four
        # batches a second must not wait 64 of them
        first = batcher._probe_task
        await batcher.subscribers_async("by/12/x")
        assert batcher._probe_task is first     # fresh: no new probe
        batcher._probed_at -= batcher.BYPASS_PROBE_SECONDS
        await batcher.subscribers_async("by/13/x")
        assert batcher._probe_task is not first
        await batcher._probe_task
        assert batcher._since_probe <= 1
    finally:
        await batcher.close()


# -- ADR 015: a record per micro-batch, the engine's phases in it --------


def _traced_sig_batcher(tracer, **kw):
    """A MicroBatcher over a real SigEngine (device path, not the
    small-corpus router), with the tracer attached as bootstrap does."""
    from maxmq_tpu.matching.sig import SigEngine

    index = TopicIndex()
    for i in range(150):
        index.subscribe(f"cl-{i}", Subscription(filter=f"tr/{i}/+", qos=1))
        index.subscribe(f"cl-{i}", Subscription(filter=f"tr/{i}/#", qos=0))
    eng = SigEngine(index)
    eng.route_small = False
    batcher = MicroBatcher(eng, window_us=0, max_batch=64, **kw)
    batcher.tracer = eng.tracer = tracer
    return batcher


def _phases(rec) -> dict:
    return {name: (t0, t1, on_loop) for name, t0, t1, on_loop in rec.phases}


async def _one_batch(batcher, n=12, tag="x"):
    futs = [batcher.enqueue(f"tr/{i}/{tag}") for i in range(n)]
    await asyncio.gather(*futs)
    rec = futs[0]._t_batch
    assert all(f._t_batch is rec for f in futs)     # one object a batch
    assert rec.n == n
    return rec


async def test_bypassed_batch_records_the_host_phases_on_the_loop():
    from maxmq_tpu.trace import PipelineTracer

    tracer = PipelineTracer(sample_n=1)
    batcher = _traced_sig_batcher(tracer)
    try:
        batcher._device_rtt, batcher._rtt_samples = 10.0, 2    # bypass wins
        rec = await _one_batch(batcher)
        assert rec.via == "host" and batcher.bypasses == 12
        ph = _phases(rec)
        assert set(ph) == {"match_host", "match_prep", "match_probe",
                           "match_decode"}
        assert all(on_loop for _t0, _t1, on_loop in ph.values())
        h0, h1, _ = ph["match_host"]
        inner = [ph[k] for k in ("match_prep", "match_probe",
                                 "match_decode")]
        assert h0 <= min(t0 for t0, _t1, _ in inner)
        assert h1 >= max(t1 for _t0, t1, _ in inner)
        assert sum(t1 - t0 for t0, t1, _ in inner) <= h1 - h0
        # the trie walk, when the cost model prefers it, says so
        batcher._trie_cost = 1e-9
        rec = await _one_batch(batcher, tag="y")
        assert rec.via == "trie" and set(_phases(rec)) == {"match_host"}
        assert tracer.batch_hist["match_host"].count == 2
        assert tracer.batch_hist["match_prep"].count == 1
    finally:
        await batcher.close()


async def test_whole_batch_device_call_records_round_trip_and_hop():
    from maxmq_tpu.trace import PipelineTracer

    tracer = PipelineTracer(sample_n=1)
    batcher = _traced_sig_batcher(tracer, pipeline_depth=1,
                                  cpu_bypass=False)
    try:
        rec = await _one_batch(batcher)
        assert rec.via == "whole"
        ph = _phases(rec)
        assert {"match_prep", "match_dispatch", "match_fetch",
                "match_decode", "device_rtt", "match_hop"} <= set(ph)
        dur = {k: t1 - t0 for k, (t0, t1, _) in ph.items()}
        assert dur["device_rtt"] >= dur["match_dispatch"] + dur["match_fetch"]
        assert ph["device_rtt"][0] == ph["match_dispatch"][0]
        assert ph["device_rtt"][1] == ph["match_fetch"][1]
        # the engine ran off the loop; the hop was recorded on it, from
        # result-ready on the worker thread
        assert not ph["match_dispatch"][2] and not ph["match_fetch"][2]
        assert ph["match_hop"][2] and ph["match_hop"][0] == rec.ready_ns
        assert ph["match_hop"][0] >= ph["match_decode"][1]
        assert batcher.device_round_trip == dur["device_rtt"] / 1e9 > 0
    finally:
        await batcher.close()


async def test_pipelined_path_records_no_device_rtt():
    """Dispatch and collect lie across a loop hop there: only the two
    phases are recorded, never a sum."""
    from maxmq_tpu.trace import PipelineTracer

    tracer = PipelineTracer(sample_n=1)
    batcher = _traced_sig_batcher(tracer, cpu_bypass=False)    # depth 3
    try:
        rec = await _one_batch(batcher)
        assert rec.via == "device"
        ph = _phases(rec)
        assert {"match_prep", "match_dispatch", "match_fetch",
                "match_decode", "match_hop"} <= set(ph)
        assert "device_rtt" not in ph
        assert batcher.device_round_trip == 0.0
        assert tracer.batch_hist["device_rtt"].count == 0
    finally:
        await batcher.close()


async def test_shadow_probe_is_a_record_of_its_own():
    from maxmq_tpu.trace import PipelineTracer

    tracer = PipelineTracer(sample_n=1)
    batcher = _traced_sig_batcher(tracer)
    try:
        batcher._device_rtt, batcher._rtt_samples = 10.0, 2
        batcher._since_probe = batcher.BYPASS_PROBE_EVERY - 1
        rec = await _one_batch(batcher)
        await batcher._probe_task
        probe = rec.probe
        assert probe is not None and probe.of is rec and probe.closed
        assert probe.id != rec.id and probe.via == "device"
        assert {"match_dispatch", "match_fetch", "device_rtt",
                "match_hop"} <= set(_phases(probe))
        shown = tracer.report()["batches"]
        assert [b["id"] for b in shown] == [rec.id, probe.id]
        assert shown[1]["shadow"] is True and shown[1]["of"] == rec.id
        assert "shadow" not in shown[0] and shown[0]["via"] == "host"
        assert {p["name"] for p in shown[0]["phases"]} >= {"match_host"}
        assert all(p["on_loop"] for p in shown[0]["phases"])
    finally:
        await batcher.close()


async def test_supervisor_forwards_the_batch_record():
    from maxmq_tpu.matching.supervisor import SupervisedMatcher
    from maxmq_tpu.trace import PipelineTracer

    tracer = PipelineTracer(sample_n=1)
    batcher = MicroBatcher(FakeEngine(), window_us=0, cpu_bypass=False)
    batcher.tracer = tracer
    sup = SupervisedMatcher(batcher, deadline_ms=2000)
    try:
        out = sup.enqueue("a/b")
        assert await out == "result:a/b"
        rec = out._t_batch
        assert rec.id == 1 and rec.n == 1 and rec.via == "whole"
        assert out._t_dispatch == rec.t0_ns and out._t_done >= rec.t0_ns
        # a cache hit has no batch: the answerer's name is forwarded
        hit = sup.enqueue("a/b")
        await hit
        assert hit._t_via == "cache" and hit._t_done
        assert not hasattr(hit, "_t_batch")
        assert not hasattr(hit, "_t_dispatch")
    finally:
        await batcher.close()


async def test_batch_ring_is_bounded_by_trace_ring():
    from maxmq_tpu.trace import PipelineTracer

    tracer = PipelineTracer(sample_n=1, ring=4)
    batcher = MicroBatcher(FakeEngine(), window_us=0, max_batch=1,
                           cpu_bypass=False)
    batcher.tracer = tracer
    try:
        await asyncio.gather(
            *[batcher.subscribers_async(f"r/{i}") for i in range(10)])
        shown = tracer.report()["batches"]
        assert [b["id"] for b in shown] == [7, 8, 9, 10]
        assert tracer.allocations == 10     # records count as allocations
    finally:
        await batcher.close()


async def test_no_marks_no_records_with_sampling_off():
    from maxmq_tpu.trace import PipelineTracer

    tracer = PipelineTracer(sample_n=0)
    batcher = _traced_sig_batcher(tracer)
    try:
        batcher._device_rtt, batcher._rtt_samples = 10.0, 2
        futs = [batcher.enqueue(f"tr/{i}/x") for i in range(4)]
        await asyncio.gather(*futs)
        futs.append(batcher.enqueue("tr/1/x"))          # a cache hit
        for fut in futs:
            assert not [a for a in ("_t_dispatch", "_t_done", "_t_batch",
                                    "_t_via") if hasattr(fut, a)]
        assert tracer.allocations == 0
        assert tracer.report()["batches"] == []
        assert all(h.count == 0 for h in tracer.batch_hist.values())
    finally:
        await batcher.close()
