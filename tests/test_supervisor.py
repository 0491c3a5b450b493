"""ADR-011 addendum: the supervisor keeps its ladder beside the inner
matcher's own future, not around it.

The async surface (``SupervisedMatcher.enqueue``) hands the publish
pipeline the MicroBatcher's future, keeps every topic's deadline on one
queue under one timer, and learns of a failed batch where the batcher
does. These tests hold it to what the wrapper guaranteed: an answer by
the deadline, bit-equal to the trie on every fallback, the breaker's
thresholds and counters, the ADR-015 marks — and to what it no longer
costs: a loop crossing, a second future and a timer per topic."""

import asyncio
import threading

import pytest

from test_broker_system import connect, running_broker
from test_faults import small_corpus as corpus
from matching_helpers import EngineStub, normalize

from maxmq_tpu.matching.batcher import MicroBatcher
from maxmq_tpu.matching.service import ServiceMatcher
from maxmq_tpu.matching.supervisor import (BREAKER_CLOSED,
                                           BREAKER_HALF_OPEN,
                                           BREAKER_OPEN, SupervisedMatcher)
from maxmq_tpu.matching.trie import TopicIndex
from maxmq_tpu.trace import PipelineTracer


class StubEngine(EngineStub):
    """An engine whose answers are the trie's, and which can be told to
    raise, or to hang until released."""

    def __init__(self, index: TopicIndex | None = None) -> None:
        self.index = index if index is not None else corpus()
        self.calls = 0
        self.raising = False
        self.hang: threading.Event | None = None

    def subscribers_batch(self, topics):
        self.calls += 1
        if self.hang is not None:
            self.hang.wait(5)
        if self.raising:
            raise RuntimeError("device on fire")
        return [self.index.subscribers(t) for t in topics]


class BareEnqueue:
    """An inner with ``enqueue`` and no way to report a failed batch."""

    def __init__(self, batcher: MicroBatcher) -> None:
        self.batcher = batcher
        self.index = batcher.index

    def enqueue(self, topic):
        return self.batcher.enqueue(topic)


def device_batcher(engine=None, **kw) -> MicroBatcher:
    """Every batch goes to the engine in the executor."""
    kw.setdefault("window_us", 0)
    return MicroBatcher(engine or StubEngine(), cpu_bypass=False, **kw)


def same(got, idx: TopicIndex, topic: str) -> bool:
    return normalize(got) == normalize(idx.subscribers(topic))


class LoopTicks:
    """Counts loop iterations: a ``call_soon`` that re-arms itself runs
    once in each."""

    def __init__(self) -> None:
        self.n = 0
        self._live = True
        asyncio.get_running_loop().call_soon(self._tick)

    def _tick(self) -> None:
        self.n += 1
        if self._live:
            asyncio.get_running_loop().call_soon(self._tick)

    def stop(self) -> None:
        self._live = False


# -- (a) one crossing between the answer and the awaiter ----------------


@pytest.mark.parametrize("surface, crossings", [("direct", 1),
                                                ("wrapped", 2)])
async def test_awaiter_resumes_the_iteration_after_the_settle(
        surface, crossings):
    batcher = device_batcher()
    inner = batcher if surface == "direct" else BareEnqueue(batcher)
    sup = SupervisedMatcher(inner, deadline_ms=2000)
    ticks, settled_at, handed = LoopTicks(), [], []
    settle, enqueue = batcher._settle, batcher.enqueue

    def noting_settle(*a):
        settled_at.append(ticks.n)
        settle(*a)

    def noting_enqueue(topic):
        handed.append(enqueue(topic))
        return handed[-1]

    batcher._settle, batcher.enqueue = noting_settle, noting_enqueue
    try:
        fut = sup.enqueue("f/1/x")
        got = await fut
        resumed_at = ticks.n
        assert same(got, batcher.index, "f/1/x")
        assert resumed_at - settled_at[0] == crossings
        # the pipeline holds the batcher's own future, or a second one
        assert (fut is handed[0]) == (surface == "direct")
        assert sup.wrapped_topics == (surface == "wrapped")
        assert sup.deadline_timers_armed == 1
    finally:
        ticks.stop()
        await batcher.close()


# -- (b) a hung inner: one timer, every topic by its own deadline -------


async def test_hung_inner_every_topic_by_its_own_deadline():
    eng = StubEngine()
    eng.hang = threading.Event()
    batcher = device_batcher(eng)
    sup = SupervisedMatcher(batcher, deadline_ms=200, breaker_threshold=5,
                            backoff_initial_s=30.0)
    loop = asyncio.get_running_loop()
    bursts, per_burst = 5, 40
    waits: list[float] = []
    futs = []
    try:
        for b in range(bursts):
            for i in range(per_burst):
                fut = sup.enqueue(f"f/{i % 12}/x")
                fut.add_done_callback(
                    lambda _f, t0=loop.time(): waits.append(
                        loop.time() - t0))
                futs.append((f"f/{i % 12}/x", fut))
            await asyncio.sleep(0.01)              # 50 ms in all
        assert sup.breaker_state == BREAKER_CLOSED and not waits
        results = await asyncio.gather(*[f for _t, f in futs])
        n = bursts * per_burst
        for (topic, _f), got in zip(futs, results):
            assert same(got, eng.index, topic)
        # by its own deadline, not the first topic's nor the last's
        assert min(waits) >= 0.199 and max(waits) < 0.35, (min(waits),
                                                           max(waits))
        assert sup.deadline_fallbacks == n and sup.error_fallbacks == 0
        assert 1 <= sup.deadline_timers_armed <= 2 * bursts
        # the breaker opened at the threshold, as it did per topic
        assert sup.breaker_state == BREAKER_OPEN
        assert sup.breaker_trips == 1
        at_once = sup.enqueue("f/3/x")
        assert at_once.done() and same(at_once.result(), eng.index, "f/3/x")
        assert sup.breaker_fallbacks == 1
        assert not sup._watched and sup._sweep_timer is None
    finally:
        eng.hang.set()
        await batcher.close()


# -- (c) a batch that raises is answered where the batcher learns -------


def _raising_whole_batch():
    eng = StubEngine()
    eng.raising = True
    return device_batcher(eng, window_us=2000), eng


def _raising_bypass():
    eng = StubEngine()
    eng.raising = True
    batcher = MicroBatcher(eng, window_us=2000)
    batcher._device_rtt = 1.0              # every batch is bypassed
    return batcher, eng


@pytest.mark.parametrize("make", [_raising_whole_batch, _raising_bypass])
async def test_raising_batch_answered_from_the_trie(make):
    batcher, eng = make()
    sup = SupervisedMatcher(batcher, deadline_ms=2000,
                            breaker_threshold=100)
    topics = [f"f/{i}/x" for i in range(8)] + ["g/nope", "f/3/zzz"]
    try:
        futs = [sup.enqueue(t) for t in topics]
        results = await asyncio.gather(*futs)   # none raises
        for topic, got in zip(topics, results):
            assert same(got, eng.index, topic)
        assert sup.error_fallbacks == len(topics)
        assert len(sup._failures) == len(topics)    # one each, as before
        assert batcher.errors == 1 and batcher.batches == 1
        assert sup.deadline_fallbacks == 0 and sup.wrapped_topics == 0
    finally:
        await batcher.close()


async def test_dead_service_transport_answered_from_the_trie():
    idx = corpus()
    service = ServiceMatcher("/nonexistent/matcher.sock")
    sup = SupervisedMatcher(service, index=idx, deadline_ms=2000,
                            breaker_threshold=100)
    try:
        fut = sup.enqueue("f/2/x")
        assert fut.done() and same(fut.result(), idx, "f/2/x")
        assert sup.error_fallbacks == 1 and service.fallbacks == 1
        assert sup.wrapped_topics == 0 and not sup._watched
    finally:
        await service.close()


async def test_pipeline_never_sees_the_batch_error():
    async with running_broker() as broker:
        sub = await connect(broker, "s1")
        await sub.subscribe(("e2e/+/t", 1))
        eng = StubEngine(broker.topics)
        eng.raising = True
        batcher = device_batcher(eng)
        sup = SupervisedMatcher(batcher, index=broker.topics,
                                deadline_ms=2000, breaker_threshold=100)
        broker.attach_matcher(sup)
        pub = await connect(broker, "p1")
        for i in range(4):
            await pub.publish(f"e2e/f{i}/t", b"m%d" % i, qos=1)
        for i in range(4):
            msg = await sub.next_message(timeout=10)
            assert msg.payload == b"m%d" % i        # order preserved
        assert sup.error_fallbacks == 4
        assert broker.matcher_degrades == 0
        await pub.disconnect()
        await sub.disconnect()
        await batcher.close()


# -- (d) the breaker routes as it did -----------------------------------


def _open(sup, until=0.0):
    sup._state, sup._open_until = BREAKER_OPEN, until


async def test_open_breaker_answers_at_once():
    batcher = device_batcher()
    sup = SupervisedMatcher(batcher, deadline_ms=2000)
    _open(sup, float("inf"))
    try:
        fut = sup.enqueue("f/1/x")
        assert fut.done() and same(fut.result(), batcher.index, "f/1/x")
        assert sup.breaker_fallbacks == 1 and batcher.batches == 0
        assert not sup._watched and sup.deadline_timers_armed == 0
    finally:
        await batcher.close()


@pytest.mark.parametrize("outcome", ["success", "cache_hit", "error",
                                     "deadline", "cancelled"])
async def test_half_open_admits_one_probe(outcome):
    eng = StubEngine()
    batcher = device_batcher(eng)
    sup = SupervisedMatcher(batcher, backoff_initial_s=1.0,
                            deadline_ms=60 if outcome == "deadline" else 5000,
                            breaker_threshold=100)
    try:
        if outcome == "cache_hit":
            await sup.enqueue("f/1/x")
        _open(sup)
        eng.raising = outcome == "error"
        if outcome in ("deadline", "cancelled"):
            eng.hang = threading.Event()
        probe = sup.enqueue("f/1/x")
        assert sup.breaker_state == BREAKER_HALF_OPEN
        assert sup._probe_inflight
        beside = sup.enqueue("f/2/x")           # not a second probe
        assert beside.done() and sup.breaker_fallbacks == 1
        if outcome == "cancelled":
            probe.cancel()
            await asyncio.sleep(0)
            await asyncio.sleep(0)
            # neither success nor failure: the slot is free again
            assert not sup._probe_inflight
            assert sup.breaker_state == BREAKER_OPEN
            assert sup.breaker_recoveries == 0
            eng.hang.set()
            eng.hang = None
            assert same(await sup.enqueue("f/3/x"), eng.index, "f/3/x")
            await asyncio.sleep(0)
            assert sup.breaker_state == BREAKER_CLOSED
            return
        assert same(await probe, eng.index, "f/1/x")
        await asyncio.sleep(0)
        assert not sup._probe_inflight and sup._probe_fut is None
        if outcome in ("success", "cache_hit"):
            assert sup.breaker_state == BREAKER_CLOSED
            assert sup.breaker_recoveries == 1
            assert eng.calls == 1
        else:
            # re-opened, and backing off harder before the next probe
            assert sup.breaker_state == BREAKER_OPEN
            assert sup._backoff == 2.0 and sup.breaker_recoveries == 0
            assert (sup.error_fallbacks, sup.deadline_fallbacks) == (
                (1, 0) if outcome == "error" else (0, 1))
            assert sup.enqueue("f/4/x").done()  # trie until the backoff
    finally:
        if eng.hang is not None:
            eng.hang.set()
        await batcher.close()


# -- (e) the ADR-015 marks are on the future the pipeline holds ---------


def _cache_hit(batcher, eng):
    batcher._cache.put("f/1/x", batcher._subs_version(),
                       eng.index.subscribers("f/1/x"))


def _bypass_host(batcher, eng):
    batcher._device_rtt, batcher._trie_cost = 1.0, 1.0


def _bypass_trie(batcher, eng):
    batcher._device_rtt, batcher._trie_cost = 1.0, 1e-9


def _hang(batcher, eng):
    eng.hang = threading.Event()


@pytest.mark.parametrize("via, engine, arrange", [
    ("cache", StubEngine, _cache_hit),
    ("host", StubEngine, _bypass_host),
    ("trie", StubEngine, _bypass_trie),
    ("fallback", StubEngine, _hang),
])
async def test_trace_marks_on_the_returned_future(via, engine, arrange):
    eng = engine()
    batcher = MicroBatcher(eng, window_us=0, cpu_bypass=via != "fallback")
    batcher.tracer = PipelineTracer(sample_n=1)
    sup = SupervisedMatcher(batcher, breaker_threshold=100,
                            deadline_ms=50 if via == "fallback" else 5000)
    arrange(batcher, eng)
    try:
        fut = sup.enqueue("f/1/x")
        assert same(await fut, eng.index, "f/1/x")
        assert fut._t_done
        rec = getattr(fut, "_t_batch", None)
        if via in ("host", "trie"):
            assert rec.via == via and fut._t_dispatch == rec.t0_ns
            assert fut._t_done >= fut._t_dispatch
            assert not hasattr(fut, "_t_via")
        else:
            # no batch answered it: the answerer is named on the future
            assert rec is None and fut._t_via == via
            assert hasattr(fut, "_t_dispatch") == (via == "fallback")
        assert sup.wrapped_topics == 0
    finally:
        if eng.hang is not None:
            eng.hang.set()
        await batcher.close()


# -- (f) nothing is left behind -----------------------------------------


async def test_ten_thousand_enqueues_leave_nothing_behind():
    batcher = device_batcher(max_batch=512)
    sup = SupervisedMatcher(batcher, deadline_ms=300)
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    try:
        for chunk in range(20):
            futs = [sup.enqueue(f"t/{chunk}/{i}") for i in range(500)]
            await asyncio.gather(*futs)
        took = loop.time() - t0
        assert sup.deadline_fallbacks == 0 and sup.error_fallbacks == 0
        # one timer at a time, however many topics are in flight
        assert 1 <= sup.deadline_timers_armed <= took / 0.3 + 2
        await asyncio.sleep(0.35)           # the last one armed fires
        assert not sup._watched and sup._sweep_timer is None
        assert not [h for h in loop._scheduled if not h._cancelled
                    and getattr(h._callback, "__self__", None) is sup]
    finally:
        await batcher.close()


# -- the counters that say it engages -----------------------------------


async def test_deadline_counters_exposed():
    from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities
    from maxmq_tpu.metrics import Registry, register_broker_metrics

    broker = Broker(BrokerOptions(
        capabilities=Capabilities(sys_topic_interval=0)))
    batcher = device_batcher(StubEngine(broker.topics))
    sup = SupervisedMatcher(batcher, index=broker.topics, deadline_ms=2000)
    broker.attach_matcher(sup)
    try:
        await asyncio.gather(*[sup.enqueue(f"t/{i}") for i in range(64)])
        reg = Registry()
        register_broker_metrics(reg, broker)
        text = reg.expose()
        assert "maxmq_matcher_deadline_timers_armed_total 1" in text
        assert "maxmq_matcher_wrapped_topics_total 0" in text
    finally:
        await batcher.close()
