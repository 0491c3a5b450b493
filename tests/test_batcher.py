"""Tests for the micro-batching matcher front end (SURVEY §7 stage 4: the
publish micro-batch queue in front of the device matcher)."""

from __future__ import annotations

import asyncio

import pytest

from maxmq_tpu.matching.batcher import MicroBatcher
from maxmq_tpu.matching.trie import TopicIndex
from maxmq_tpu.protocol.packets import Subscription


class FakeEngine:
    """Records the batch shapes the batcher dispatches."""

    def __init__(self) -> None:
        self.index = TopicIndex()
        self.calls: list[list[str]] = []

    def subscribers_batch(self, topics):
        self.calls.append(list(topics))
        return [f"result:{t}" for t in topics]

    def subscribers(self, topic):
        return self.subscribers_batch([topic])[0]

    def refresh(self, force=False):
        return False


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


async def test_batched_dense_engine_parity():
    """End to end with the real dense device matcher: batched answers equal
    the exact CPU trie."""
    from maxmq_tpu.matching.dense import DenseEngine

    index = TopicIndex()
    for i, f in enumerate(["a/+", "a/b", "a/#", "x/y", "+/y", "$sys/#"]):
        index.subscribe(f"cl-{i}", Subscription(filter=f, qos=1))
    engine = DenseEngine(index, max_levels=6)
    batcher = MicroBatcher(engine, window_us=500, max_batch=32)
    try:
        topics = ["a/b", "a/c", "x/y", "q/y", "$sys/health", "nope"] * 3
        got = await asyncio.gather(
            *[batcher.subscribers_async(t) for t in topics])
        for topic, s in zip(topics, got):
            want = index.subscribers(topic)
            assert set(s.subscriptions) == set(want.subscriptions), topic
    finally:
        await batcher.close()


def test_batcher_delegates_sync_surface():
    eng = FakeEngine()
    batcher = MicroBatcher(eng, cpu_bypass=False)
    assert batcher.subscribers("a") == "result:a"
    assert batcher.refresh() is False
    assert batcher.index is eng.index


class SplitEngine:
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

    def refresh(self, force=False):
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
    finally:
        await batcher.close()
