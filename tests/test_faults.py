"""ADR-011 fault suite: every rung of the matcher degradation ladder
under deterministic injected faults (maxmq_tpu/faults.py).

For each fault class the ISSUE names — device exception, device hang
past the deadline, recompile failure, matcher-service socket drop,
pool-worker death — an end-to-end match/publish still completes with
results bit-equal to the CPU trie, the breaker trips after the
configured threshold, and a half-open reprobe restores the device path
once the fault clears; all of it observable through the new metrics."""

import asyncio
import io
import time

import pytest

from test_broker_system import connect, running_broker
from matching_helpers import normalize

from maxmq_tpu import faults
from maxmq_tpu.matching.batcher import MicroBatcher
from maxmq_tpu.matching.sig import SigEngine
from maxmq_tpu.matching.supervisor import (BREAKER_CLOSED, BREAKER_OPEN,
                                           SupervisedMatcher)
from maxmq_tpu.matching.trie import TopicIndex
from maxmq_tpu.protocol import Subscription
from maxmq_tpu.utils.logger import Logger


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


def small_corpus(n: int = 24) -> TopicIndex:
    idx = TopicIndex()
    for i in range(n):
        idx.subscribe(f"ex{i}", Subscription(filter=f"f/{i}/x", qos=1))
        idx.subscribe(f"pl{i}", Subscription(filter=f"f/{i}/+", qos=0))
    idx.subscribe("hash", Subscription(filter="f/#", qos=2))
    idx.subscribe("sh", Subscription(filter="$share/g/f/1/x", qos=1))
    return idx


def make_engine(idx: TopicIndex) -> SigEngine:
    eng = SigEngine(idx, auto_refresh=False)
    eng.route_small = False      # force the device path on tiny corpora
    return eng


TOPICS = ["f/1/x", "f/7/x", "f/3/zzz", "g/nope", "f/0/x"]


def assert_trie_equal(idx, results, topics=TOPICS):
    for topic, got in zip(topics, results):
        want = idx.subscribers(topic)
        assert normalize(got) == normalize(want), topic


# -- the registry itself ----------------------------------------------


def test_registry_counts_are_deterministic():
    reg = faults.FaultRegistry()
    reg.arm("x", "raise", count=2)
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            reg.fire("x")
    assert reg.fire("x") is False          # self-disarmed after 2
    assert reg.fired["x"] == 2
    # FIFO scripting: raise twice, then an action-mode entry
    reg.arm("y", "raise", count=1)
    reg.arm("y", "drop", count=1)
    with pytest.raises(faults.InjectedFault):
        reg.fire("y")
    assert reg.fire("y") is True
    assert reg.fire("y") is False


def test_registry_env_spec_parsing():
    reg = faults.FaultRegistry()
    reg.arm_from_spec("a.b:raise:2, c.d:hang:1:0.001 ,e.f:exit")
    assert reg.armed("a.b") and reg.armed("c.d") and reg.armed("e.f")
    t0 = time.perf_counter()
    assert reg.fire("c.d") is True         # hang mode sleeps delay_s
    assert time.perf_counter() - t0 < 0.5
    assert reg.fire("e.f") is True         # action mode returns True
    with pytest.raises(ValueError):
        reg.arm_from_spec("missing-mode")


# -- rung 2: device exception -> trie hedge, bit-equal ----------------


def test_device_exception_answers_bit_equal_from_trie():
    idx = small_corpus()
    sup = SupervisedMatcher(make_engine(idx), deadline_ms=0,
                            breaker_threshold=100)
    assert_trie_equal(idx, sup.subscribers_batch(TOPICS))   # healthy
    faults.arm(faults.DEVICE_MATCH, "raise", count=-1)
    assert_trie_equal(idx, sup.subscribers_batch(TOPICS))   # degraded
    assert sup.fallbacks_by_reason["error"] == len(TOPICS)
    assert sup.breaker_state == BREAKER_CLOSED              # under threshold
    faults.clear()
    assert_trie_equal(idx, sup.subscribers_batch(TOPICS))   # healed
    assert sup.fallbacks_by_reason["error"] == len(TOPICS)  # no new ones


# -- rung 1: hang past the per-batch deadline -------------------------


async def test_hang_past_deadline_served_from_trie():
    idx = small_corpus()
    eng = make_engine(idx)
    # warm the XLA compile OUTSIDE the deadline window: the supervisor
    # deadlines real calls, and the first-call compile is boot work the
    # production path pays at the quiescent point (warm_buckets)
    await asyncio.get_running_loop().run_in_executor(
        None, eng.subscribers_fixed_batch, ["f/1/x"])
    batcher = MicroBatcher(eng, window_us=0, cpu_bypass=False)
    sup = SupervisedMatcher(batcher, deadline_ms=100,
                            breaker_threshold=100)
    got = await sup.enqueue("f/1/x")                        # healthy
    assert normalize(got) == normalize(idx.subscribers("f/1/x"))
    faults.arm(faults.DEVICE_MATCH, "hang", count=-1, delay_s=0.5)
    t0 = time.perf_counter()
    got = await sup.enqueue("f/7/x")
    took = time.perf_counter() - t0
    assert normalize(got) == normalize(idx.subscribers("f/7/x"))
    assert took < 0.45, took               # answered by the deadline,
    assert sup.deadline_fallbacks == 1     # not the 500ms hang
    faults.clear()
    await asyncio.sleep(0.6)               # drain the hung executor call
    await batcher.close()


def test_sync_deadline_served_from_trie():
    idx = small_corpus()
    eng = make_engine(idx)
    eng.subscribers_batch(TOPICS)          # warm the compile
    sup = SupervisedMatcher(eng, deadline_ms=100,
                            breaker_threshold=100)
    faults.arm(faults.DEVICE_MATCH, "hang", count=1, delay_s=0.5)
    t0 = time.perf_counter()
    results = sup.subscribers_batch(TOPICS)
    assert time.perf_counter() - t0 < 0.45
    assert_trie_equal(idx, results)
    assert sup.fallbacks_by_reason["deadline"] == len(TOPICS)
    time.sleep(0.5)                        # let the hung thread finish


# -- rung 3+4: breaker trip and half-open reprobe ---------------------


def test_breaker_trips_then_half_open_reprobe_restores():
    idx = small_corpus()
    sup = SupervisedMatcher(make_engine(idx), deadline_ms=0,
                            breaker_threshold=3, breaker_window_s=10.0,
                            backoff_initial_s=0.15, backoff_max_s=0.6)
    faults.arm(faults.DEVICE_MATCH, "raise", count=-1)
    for _ in range(3):                     # threshold failures...
        assert_trie_equal(idx, sup.subscribers_batch(TOPICS))
    assert sup.breaker_state == BREAKER_OPEN    # ...trip the breaker
    assert sup.breaker_trips == 1
    # open: answered from the trie with NO device call
    fired_before = faults.REGISTRY.fired.get(faults.DEVICE_MATCH, 0)
    assert_trie_equal(idx, sup.subscribers_batch(TOPICS))
    assert faults.REGISTRY.fired.get(faults.DEVICE_MATCH, 0) \
        == fired_before
    assert sup.fallbacks_by_reason["breaker_open"] == len(TOPICS)
    # fault still present at the first reprobe: re-opens, backoff doubles
    time.sleep(0.2)
    assert_trie_equal(idx, sup.subscribers_batch(TOPICS))
    assert sup.breaker_state == BREAKER_OPEN
    assert sup._backoff == pytest.approx(0.3)
    # fault clears; the next reprobe after the backoff restores the path
    faults.clear()
    time.sleep(0.35)
    assert_trie_equal(idx, sup.subscribers_batch(TOPICS))
    assert sup.breaker_state == BREAKER_CLOSED
    assert sup.breaker_recoveries == 1
    assert sup.degraded_seconds > 0.3


# -- recompile failure: crash-safe table swap -------------------------


def test_recompile_failure_keeps_last_good_tables():
    idx = small_corpus()
    eng = make_engine(idx)
    sup = SupervisedMatcher(eng, deadline_ms=0, breaker_threshold=100)
    v0 = eng.tables.version
    idx.subscribe("late", Subscription(filter="f/9/late", qos=0))
    faults.arm(faults.DEVICE_RECOMPILE, "raise", count=2)
    assert sup.refresh(force=True) is False     # swallowed, counted
    assert sup.refresh(force=True) is False
    assert sup.refresh_failures == 2
    assert eng.tables.version == v0             # last-good still live
    # matches stay EXACT through the stale window (journal overlay)
    topics = TOPICS + ["f/9/late"]
    for topic, got in zip(topics, sup.subscribers_batch(topics)):
        assert normalize(got) == normalize(idx.subscribers(topic)), topic
    # fault exhausted: the next refresh swaps in fresh tables
    assert sup.refresh(force=True) is True
    assert eng.tables.version > v0


async def test_boot_compile_failure_fails_serve_with_device_matcher():
    """No last-good table exists at boot: a compile that fails there
    must fail serve(), not leave a broker serving from its trie behind
    a device matcher that never came up. The bucket warm rides the
    boot compile, so the first publish meets a compiled program."""
    from maxmq_tpu.bootstrap import build_broker
    from maxmq_tpu.utils.config import Config

    def restored_broker():
        conf = Config(mqtt_tcp_address="127.0.0.1:0", metrics_enabled=False,
                      mqtt_sys_topic_interval=0, matcher="sig")
        broker = build_broker(conf, Logger(out=io.StringIO(), fmt="json"))
        for i in range(400):        # past the ADR-008 trie-routed size
            broker.topics.subscribe(
                f"c{i}", Subscription(filter=f"f/{i}/#", qos=1))
        return broker

    faults.arm(faults.DEVICE_RECOMPILE, "raise", count=1)
    with pytest.raises(faults.InjectedFault):
        await restored_broker().serve()

    broker = restored_broker()      # the fault is spent: a healthy boot
    await broker.serve()
    try:
        engine = broker.matcher.engine
        assert engine.tables.version == broker.topics.sub_version
        assert engine.warm_seconds > 0          # buckets compiled at boot
        assert engine.bg_refresh_errors == 0
    finally:
        await broker.close()
        await broker.matcher.close()


def test_bucket_warm_surfaces_a_compile_error(caplog):
    """A warm that cannot compile raises on the caller's thread and is
    counted and logged from a background one; a trie-routed corpus has
    no program to warm and is not an error."""
    eng = make_engine(small_corpus())
    faults.arm(faults.DEVICE_MATCH, "raise", count=-1)
    with pytest.raises(faults.InjectedFault):
        eng.warm_buckets(64, background=False)
    eng.warm_buckets(64)
    eng.close()
    assert eng.bg_refresh_errors == 1
    assert "bucket warm failed" in caplog.text
    routed = SigEngine(TopicIndex())        # empty: ADR-008 routes it
    routed.warm_buckets(64, background=False)
    assert routed.bg_refresh_errors == 0 and routed.warm_seconds == 0


# -- matcher-service socket drop --------------------------------------


async def test_service_socket_drop_end_to_end(tmp_path):
    from maxmq_tpu.matching.service import MatcherService, ServiceMatcher

    def svc_engine(i):
        e = SigEngine(i)                   # auto-refresh: service-owned
        e.route_small = False
        return MicroBatcher(e, window_us=0, cpu_bypass=False)

    path = str(tmp_path / "m.sock")
    idx = small_corpus()
    svc = MatcherService(path, engine_factory=svc_engine)
    await svc.start()
    try:
        m = ServiceMatcher(path)
        m.RECONNECT_BACKOFF_INITIAL = 0.02
        await m.connect()

        def reseed(mm):                    # as attach_matcher_service
            for cid, sub in idx.walk_subscriptions():
                mm.forward_subscribe(cid, sub)

        m._reseed = reseed
        reseed(m)
        sup = SupervisedMatcher(m, index=idx, deadline_ms=10_000,
                                breaker_threshold=100)
        got = await sup.enqueue("f/1/x")        # healthy round trip
        assert normalize(got) == normalize(idx.subscribers("f/1/x"))
        # drop the socket server-side on the next frame: the pending
        # match errors, the supervisor answers from the trie
        faults.arm(faults.SERVICE_SOCKET, "drop", count=1)
        got = await sup.enqueue("f/7/x")
        assert normalize(got) == normalize(idx.subscribers("f/7/x"))
        assert sup.error_fallbacks >= 1
        # next enqueue sees the dead transport: trie again, and it kicks
        # the background reconnect loop (capped backoff + jitter)
        got = await sup.enqueue("f/3/zzz")
        assert normalize(got) == normalize(idx.subscribers("f/3/zzz"))
        # the transport fast-fails the ServiceMatcher counts in its own
        # ``fallbacks`` are the SAME events the supervisor counts as
        # reason="error" — they must not also appear as "overflow"
        assert sup.fallbacks_by_reason["overflow"] == 0
        assert sup.fallbacks == (sup.error_fallbacks
                                 + sup.deadline_fallbacks
                                 + sup.breaker_fallbacks)
        await asyncio.sleep(0.4)           # loop reconnects + reseeds
        served_before = svc.matches_served
        got = await sup.enqueue("f/0/x")
        assert normalize(got) == normalize(idx.subscribers("f/0/x"))
        assert svc.matches_served > served_before
        assert m.reconnects >= 1
        assert m.reconnect_attempts >= 1
        await m.close()
    finally:
        await svc.close()


# -- pool-worker death: supervised respawn + counter ------------------


async def test_pool_worker_restart_counted_and_exported():
    from maxmq_tpu.broker.workers import PoolStats, _supervise_workers
    from maxmq_tpu.metrics import Registry, register_pool_metrics

    class FakeProc:
        def __init__(self, rc=None):
            self.rc = rc

        def poll(self):
            return self.rc

    procs = [FakeProc(rc=-9), FakeProc(rc=None)]    # slot 0 was killed
    respawned = []

    def spawn(i):
        respawned.append(i)
        return FakeProc(rc=None)

    stats = PoolStats()
    boot = Logger(out=io.StringIO(), fmt="json").with_prefix("pool")
    task = asyncio.get_running_loop().create_task(
        _supervise_workers(procs, spawn, boot, stats=stats,
                           interval=0.02))
    await asyncio.sleep(0.2)
    task.cancel()
    assert respawned == [0]                # crashed slot respawned once
    assert procs[0].rc is None             # live replacement installed
    assert stats.worker_restarts == 1
    reg = Registry()
    register_pool_metrics(reg, stats)
    assert "maxmq_pool_worker_restarts_total 1" in reg.expose()


# -- observability: the new metric family renders ---------------------


def test_breaker_metrics_exposed():
    from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities
    from maxmq_tpu.metrics import Registry, register_broker_metrics

    broker = Broker(BrokerOptions(
        capabilities=Capabilities(sys_topic_interval=0)))
    for cid, sub in small_corpus().walk_subscriptions():
        broker.topics.subscribe(cid, sub)
    eng = make_engine(broker.topics)
    sup = SupervisedMatcher(MicroBatcher(eng), index=broker.topics,
                            deadline_ms=0, breaker_threshold=2,
                            backoff_initial_s=30.0)
    broker.attach_matcher(sup)
    faults.arm(faults.DEVICE_MATCH, "raise", count=-1)
    for _ in range(2):
        sup.subscribers_batch(TOPICS)      # trip the breaker
    sup.subscribers_batch(TOPICS)          # breaker-open fallbacks
    reg = Registry()
    register_broker_metrics(reg, broker)
    text = reg.expose()
    assert "maxmq_matcher_breaker_state 1" in text          # open
    assert "maxmq_matcher_breaker_trips_total 1" in text
    assert 'maxmq_matcher_fallbacks_total{reason="error"} 10' in text
    assert ('maxmq_matcher_fallbacks_total{reason="breaker_open"} 5'
            in text)
    assert 'maxmq_matcher_fallbacks_total{reason="overflow"} 0' in text
    assert "maxmq_matcher_degraded_seconds_total" in text
    assert "maxmq_matcher_refresh_failures_total 0" in text
    assert "maxmq_matcher_batch_errors_total" in text
    assert "maxmq_broker_publish_trie_degraded_total 0" in text


# -- end to end: a live MQTT publish delivers through every fault -----


async def test_publish_delivers_through_device_faults():
    """The acceptance bar: with the device path raising on every call,
    a real client's publish still delivers to the right subscribers
    (served bit-equal from the trie), the breaker trips, and clearing
    the fault restores the device path after the backoff."""
    async with running_broker() as broker:
        sub_client = await connect(broker, "s1")
        await sub_client.subscribe(("e2e/+/t", 1))
        # build (and warm) the engine AFTER the subscription exists, and
        # pin the tables (auto_refresh=False) so no mid-test rotation
        # re-pays an XLA compile against the 2s deadline; later changes
        # would be served exactly via the journal overlay
        eng = SigEngine(broker.topics, auto_refresh=False)
        eng.route_small = False
        await asyncio.get_running_loop().run_in_executor(
            None, eng.subscribers_fixed_batch, ["e2e/a/t"])
        batcher = MicroBatcher(eng, window_us=0, cpu_bypass=False)
        sup = SupervisedMatcher(batcher, index=broker.topics,
                                deadline_ms=2_000, breaker_threshold=3,
                                backoff_initial_s=0.1,
                                backoff_max_s=0.2)
        broker.attach_matcher(sup)

        pub = await connect(broker, "p1")
        await pub.publish("e2e/a/t", b"healthy", qos=1)
        msg = await sub_client.next_message(timeout=10)
        assert (msg.topic, msg.payload) == ("e2e/a/t", b"healthy")

        faults.arm(faults.DEVICE_MATCH, "raise", count=-1)
        for i in range(4):                 # past the breaker threshold
            await pub.publish(f"e2e/f{i}/t", b"faulted-%d" % i, qos=1)
        for i in range(4):
            msg = await sub_client.next_message(timeout=10)
            assert msg.payload == b"faulted-%d" % i    # order preserved
        assert sup.breaker_state == BREAKER_OPEN
        assert sup.fallbacks_by_reason["error"] >= 3

        faults.clear()
        await asyncio.sleep(0.25)          # backoff expires
        await pub.publish("e2e/r/t", b"recovered", qos=1)
        msg = await sub_client.next_message(timeout=10)
        assert msg.payload == b"recovered"
        assert sup.breaker_state == BREAKER_CLOSED
        assert sup.breaker_recoveries == 1

        await pub.disconnect()
        await sub_client.disconnect()
        await batcher.close()
