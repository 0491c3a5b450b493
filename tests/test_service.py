"""Matcher service (matching/service.py): the chip-owning process
serving matches over a unix socket to broker clients (ADR 005/006)."""

import asyncio
import os
import tempfile

import pytest

from test_broker_system import connect, running_broker
from matching_helpers import normalize

from maxmq_tpu.matching.batcher import MicroBatcher
from maxmq_tpu.matching.service import (MatcherService, ServiceMatcher,
                                        attach_matcher_service)
from maxmq_tpu.matching.sig import SigEngine
from maxmq_tpu.matching.trie import TopicIndex
from maxmq_tpu.protocol import Subscription


def _sock_path() -> str:
    return os.path.join(tempfile.mkdtemp(prefix="maxmq-svc-"), "m.sock")


async def test_service_matches_and_tracks_subscriptions():
    path = _sock_path()
    svc = MatcherService(path)
    await svc.start()
    try:
        m = ServiceMatcher(path)
        await m.connect()
        m.forward_subscribe("c1", Subscription(filter="a/+/c", qos=1))
        m.forward_subscribe("c2", Subscription(filter="a/#"))
        m.forward_subscribe(
            "c3", Subscription(filter="$share/g1/a/b/c", qos=2))
        # mirror index for the expected answer
        want_idx = TopicIndex()
        want_idx.subscribe("c1", Subscription(filter="a/+/c", qos=1))
        want_idx.subscribe("c2", Subscription(filter="a/#"))
        want_idx.subscribe("c3",
                           Subscription(filter="$share/g1/a/b/c", qos=2))
        for topic in ("a/b/c", "a/x/c", "a", "b/c"):
            got = await m.subscribers_async(topic)
            assert normalize(got) == normalize(want_idx.subscribers(topic)), \
                topic
        # ops are ordered before matches on the same connection
        m.forward_unsubscribe("c2", "a/#")
        got = await m.subscribers_async("a/zzz")
        assert "c2" not in got.subscriptions
        m.forward_drop("c1")
        got = await m.subscribers_async("a/x/c")
        assert "c1" not in got.subscriptions
        assert svc.matches_served >= 6
        await m.close()
    finally:
        await svc.close()


async def test_two_clients_share_one_service():
    """Two broker processes' worth of clients coalesce on one engine and
    see each other's subscriptions (the pool-worker shape)."""
    path = _sock_path()
    svc = MatcherService(
        path, engine_factory=lambda idx: MicroBatcher(
            SigEngine(idx), window_us=0))
    await svc.start()
    try:
        m1, m2 = ServiceMatcher(path), ServiceMatcher(path)
        await m1.connect()
        await m2.connect()
        m1.forward_subscribe("w1-cl", Subscription(filter="t/+"))
        await m1.subscribers_async("t/x")     # barrier: op applied
        got = await m2.subscribers_async("t/x")
        assert "w1-cl" in got.subscriptions
        await m1.close()
        await m2.close()
    finally:
        await svc.close()


async def test_broker_attached_to_matcher_service():
    """Full path: MQTT clients against a broker whose matching runs in
    the service process-equivalent (same loop here; the socket is real)."""
    path = _sock_path()
    svc = MatcherService(path)
    await svc.start()
    try:
        async with running_broker() as broker:
            matcher = await attach_matcher_service(broker, path)
            sub = await connect(broker, "svc-sub")
            await sub.subscribe(("svc/+/x", 0))
            pub = await connect(broker, "svc-pub")
            await pub.publish("svc/a/x", b"hello")
            msg = await sub.next_message(timeout=10)
            assert msg.topic == "svc/a/x" and msg.payload == b"hello"
            # unsubscribe stops delivery through the service too
            await sub.unsubscribe("svc/+/x")
            await pub.publish("svc/a/x", b"again")
            await asyncio.sleep(0.2)
            assert sub.messages.empty()
            await sub.disconnect()
            await pub.disconnect()
            await matcher.close()
    finally:
        await svc.close()


async def test_run_server_with_service_matcher(tmp_path):
    """Bootstrap path: matcher = "service" connects the broker to an
    external matcher service socket (maxmq matcher-service)."""
    import asyncio as aio

    from maxmq_tpu.bootstrap import run_server
    from maxmq_tpu.mqtt_client import MQTTClient
    from maxmq_tpu.utils.config import Config
    from test_bootstrap import quiet_logger

    path = str(tmp_path / "m.sock")
    svc = MatcherService(path)
    await svc.start()
    try:
        conf = Config(mqtt_tcp_address="127.0.0.1:18845",
                      metrics_enabled=False, matcher="service",
                      matcher_socket=path, mqtt_sys_topic_interval=0)
        ready, stop = aio.Event(), aio.Event()
        task = aio.create_task(
            run_server(conf, quiet_logger(), ready=ready, stop=stop))
        await aio.wait_for(ready.wait(), timeout=10)
        c = MQTTClient(client_id="svc-boot")
        await c.connect("127.0.0.1", 18845)
        await c.subscribe(("sb/#", 0))
        await c.publish("sb/x", b"via-service")
        msg = await c.next_message(timeout=5)
        assert msg.payload == b"via-service"
        assert svc.matches_served >= 1
        await c.disconnect()
        stop.set()
        await aio.wait_for(task, timeout=15)
    finally:
        await svc.close()


async def test_service_loss_degrades_to_trie_then_reconnects(tmp_path):
    """Service crash mid-flight: publishes degrade to the broker's CPU
    trie (no hangs, no drops); a restarted service at the same path is
    picked up by the background reconnect and re-seeded."""
    path = str(tmp_path / "m.sock")
    svc = MatcherService(path)
    await svc.start()
    async with running_broker() as broker:
        matcher = await attach_matcher_service(broker, path)
        sub = await connect(broker, "rl-sub")
        await sub.subscribe(("rl/#", 0))
        pub = await connect(broker, "rl-pub")
        await pub.publish("rl/1", b"a")
        assert (await sub.next_message(timeout=10)).payload == b"a"

        await svc.close()                      # service dies
        await asyncio.sleep(0.1)
        await pub.publish("rl/2", b"b")        # trie fallback delivers
        assert (await sub.next_message(timeout=10)).payload == b"b"

        svc2 = MatcherService(path)            # service comes back
        await svc2.start()
        try:
            for i in range(50):                # reconnect is lazy: each
                await pub.publish(f"rl/r{i}", b"c")   # publish retries
                await sub.next_message(timeout=10)
                if svc2.matches_served:
                    break
                await asyncio.sleep(0.05)
            assert svc2.matches_served > 0, "reconnect never happened"
            assert svc2.subs_applied >= 1      # re-seeded rl/# for rl-sub
        finally:
            await svc2.close()
        await sub.disconnect()
        await pub.disconnect()
        await matcher.close()


async def test_attach_seeds_preexisting_subscriptions(tmp_path):
    """Subscriptions installed WITHOUT the subscribe hooks (the storage
    restore path) must still reach the service via the index walk."""
    path = str(tmp_path / "m.sock")
    svc = MatcherService(path)
    await svc.start()
    try:
        async with running_broker() as broker:
            # as _restore_from_storage does: direct index install
            broker.topics.subscribe(
                "persisted-cl", Subscription(filter="pr/+", qos=1))
            matcher = await attach_matcher_service(broker, path)
            got = await matcher.subscribers_async("pr/x")
            assert "persisted-cl" in got.subscriptions
            await matcher.close()
    finally:
        await svc.close()


async def test_service_matcher_topic_cache(tmp_path):
    """Repeated topics resolve from the version-keyed cache without a
    socket round trip; a subscription change invalidates."""
    path = str(tmp_path / "m.sock")
    svc = MatcherService(path)
    await svc.start()
    try:
        async with running_broker() as broker:
            matcher = await attach_matcher_service(broker, path)
            sub = await connect(broker, "tc-sub")
            await sub.subscribe(("tc/#", 0))
            r1 = await matcher.subscribers_async("tc/x")
            served = svc.matches_served
            r2 = await matcher.subscribers_async("tc/x")   # cache hit
            assert matcher.cache_hits == 1
            assert svc.matches_served == served            # no round trip
            assert "tc-sub" in r1.subscriptions and r1 == r2
            await sub.subscribe(("tc/x", 1))               # version bump
            r3 = await matcher.subscribers_async("tc/x")
            assert svc.matches_served > served
            assert r3.subscriptions["tc-sub"].qos == 1
            await sub.disconnect()
            await matcher.close()
    finally:
        await svc.close()


async def test_takeover_refcounted_across_connections():
    """Cross-worker session takeover (ADVICE r03 high): worker B
    re-subscribes (cid, filter) on its connection, then worker A's
    takeover-driven drop arrives — the index entry must survive until
    the LAST owning connection releases it, in every op interleaving."""
    path = _sock_path()
    svc = MatcherService(path)
    await svc.start()
    try:
        a, b = ServiceMatcher(path), ServiceMatcher(path)
        await a.connect()
        await b.connect()
        sub = Subscription(filter="tk/+", qos=1)
        a.forward_subscribe("cl", sub)
        await a.subscribers_async("tk/x")          # barrier: op applied
        # takeover: B re-subscribes, then A's stale drop arrives
        b.forward_subscribe("cl", sub)
        await b.subscribers_async("tk/x")
        a.forward_drop("cl")
        await a.subscribers_async("tk/x")
        got = await b.subscribers_async("tk/x")
        assert "cl" in got.subscriptions, \
            "stale drop removed a re-owned subscription"
        # A's connection closing entirely must not purge B's entry either
        await a.close()
        await asyncio.sleep(0.1)
        got = await b.subscribers_async("tk/y")
        assert "cl" in got.subscriptions
        # the LAST owner's drop does release the entry
        b.forward_drop("cl")
        got = await b.subscribers_async("tk/x")
        assert "cl" not in got.subscriptions
        assert svc._owners == {}, "owner refs leaked"
        await b.close()
    finally:
        await svc.close()


async def test_unsub_is_authoritative_across_owners():
    """An explicit UNSUB stops matching IMMEDIATELY even while a stale
    connection still holds an ownership ref (a wedged old worker must
    not keep an unsubscribed client receiving deliveries) — and the
    stale owner's eventual death must not tear down a LATER re-subscribe
    (generation guard)."""
    path = _sock_path()
    svc = MatcherService(path)
    await svc.start()
    try:
        a, b = ServiceMatcher(path), ServiceMatcher(path)
        await a.connect()
        await b.connect()
        sub = Subscription(filter="ur/+", qos=1)
        a.forward_subscribe("cl", sub)             # stale-owner-to-be
        await a.subscribers_async("ur/x")
        b.forward_subscribe("cl", sub)             # takeover re-own
        await b.subscribers_async("ur/x")
        b.forward_unsubscribe("cl", "ur/+")        # client unsubscribed
        got = await b.subscribers_async("ur/x")
        assert "cl" not in got.subscriptions, \
            "unsub must take effect immediately, not at last-owner death"
        # client re-subscribes on B; A's BUFFERED unsub flushes late —
        # generation-stale, it must not tear down B's live entry
        b.forward_subscribe("cl", sub)
        await b.subscribers_async("ur/x")
        a.forward_unsubscribe("cl", "ur/+")
        await a.subscribers_async("ur/x")
        got = await b.subscribers_async("ur/x")
        assert "cl" in got.subscriptions, \
            "stale buffered unsub removed a re-owned entry"
        # ... and A (wedged all along) finally dies — same guarantee
        await a.close()
        await asyncio.sleep(0.1)
        got = await b.subscribers_async("ur/y")
        assert "cl" in got.subscriptions, \
            "stale owner death removed a re-subscribed entry"
        await b.close()
    finally:
        await svc.close()


async def test_protocol_error_closes_transport_before_reconnect():
    """ADVICE r03 medium: a protocol error must CLOSE the old transport
    (not just null it) so the server purges the dead connection's state;
    the reconnect reseed then repopulates it without fd leaks."""
    path = _sock_path()
    svc = MatcherService(path)
    await svc.start()
    async with running_broker() as broker:
        matcher = await attach_matcher_service(broker, path)
        sub = await connect(broker, "pe-sub")
        await sub.subscribe(("pe/#", 0))
        await matcher.subscribers_async("pe/x")    # round trip ok
        old_writer = matcher._writer
        # inject garbage into the reader path by closing the server side:
        # force a protocol error instead via a malformed internal frame
        matcher._reader.feed_data(b"\x00\x00\x00\x02\x63{")  # bad frame
        await asyncio.sleep(0.2)
        assert matcher._writer is None
        assert old_writer.is_closing(), "old transport leaked"
        # next publish degrades to trie and kicks a reconnect that
        # replays subscriptions on a FRESH connection
        pub = await connect(broker, "pe-pub")
        for i in range(50):
            await pub.publish(f"pe/r{i}", b"x")
            await sub.next_message(timeout=10)
            if matcher.reconnects:
                break
            await asyncio.sleep(0.05)
        assert matcher.reconnects >= 1
        got = await matcher.subscribers_async("pe/q")
        assert "pe-sub" in got.subscriptions
        await sub.disconnect()
        await pub.disconnect()
        await matcher.close()
    await svc.close()


async def test_cli_matcher_service_command(tmp_path):
    """`maxmq matcher-service` serves a usable socket (subprocess)."""
    import os
    import signal
    import subprocess
    import sys

    path = str(tmp_path / "cli.sock")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")    # as conftest.py
    proc = subprocess.Popen(
        [sys.executable, "-m", "maxmq_tpu", "matcher-service",
         "--socket", path],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, stderr=subprocess.PIPE)
    try:
        for _ in range(100):
            if os.path.exists(path):
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError("service socket never appeared")
        m = ServiceMatcher(path)
        await m.connect()
        m.forward_subscribe("cli-c", Subscription(filter="cli/+"))
        got = await m.subscribers_async("cli/x")
        assert "cli-c" in got.subscriptions
        await m.close()
    finally:
        proc.send_signal(signal.SIGINT)
        proc.wait(timeout=10)


async def test_service_encode_memo_reuses_fragments():
    """Shared match results serialize once: repeated topics (same cached
    result object) must reuse the JSON fragment, with byte-identical
    decoded answers either way."""
    path = _sock_path()
    svc = MatcherService(path)
    await svc.start()
    try:
        m = ServiceMatcher(path)
        await m.connect()
        # no index attached -> the client topic cache stays off and
        # every match goes to the wire
        for i in range(40):
            m.forward_subscribe(f"f{i}", Subscription(filter="em/#",
                                                      qos=1))
        first = await m.subscribers_async("em/x")
        assert svc.enc_hits == 0
        for _ in range(3):
            again = await m.subscribers_async("em/x")
            assert normalize(again) == normalize(first)
        assert svc.enc_hits >= 3, svc.enc_hits
        # a subscription change rotates the result object -> fresh frag
        m.forward_subscribe("late", Subscription(filter="em/x", qos=0))
        hits_before = svc.enc_hits
        got = await m.subscribers_async("em/x")
        assert "late" in got.subscriptions
        assert svc.enc_hits == hits_before  # new result: memo miss
        await m.close()
    finally:
        await svc.close()


async def test_restart_mid_match_reseed_race(tmp_path):
    """The ADR-011 reconnect/reseed race: restart the service while a
    match is IN FLIGHT. The pending future must error (trie fallback
    upstream), the client result cache must be invalidated, and the
    reconnect must replay the live subscription set exactly once."""
    from maxmq_tpu.matching.trie import subs_version

    path = str(tmp_path / "m.sock")

    class HangingMatcher:                    # never answers: the match
        async def subscribers_async(self, topic):   # is mid-flight when
            await asyncio.Event().wait()            # the service dies

    svc = MatcherService(path, engine_factory=lambda idx: HangingMatcher())
    await svc.start()

    idx = TopicIndex()
    idx.subscribe("rc1", Subscription(filter="rr/+", qos=1))
    idx.subscribe("rc2", Subscription(filter="rr/#", qos=0))
    m = ServiceMatcher(path)
    m.RECONNECT_BACKOFF_INITIAL = 0.02
    m.index = idx
    reseeds = []

    def reseed(mm):
        reseeds.append(1)
        for cid, sub in idx.walk_subscriptions():
            mm.forward_subscribe(cid, sub)

    m._reseed = reseed
    await m.connect()
    reseed(m)                                # attach-time seed (as prod)
    ver = subs_version(idx)
    m._cache.put("rr/x", ver, idx.subscribers("rr/x"))   # warm cache

    fut = m.enqueue("rr/x2")                 # in flight (never answered)
    await asyncio.sleep(0.1)
    assert not fut.done() and m._pending
    await svc.close()                        # restart begins mid-match
    with pytest.raises((ConnectionError, RuntimeError)):
        await asyncio.wait_for(fut, timeout=5)   # pending future errors
    assert not m._pending
    assert m._cache.get("rr/x", ver) is None     # cache invalidated

    svc2 = MatcherService(path)              # service comes back
    await svc2.start()
    try:
        reseeds.clear()
        with pytest.raises((ConnectionError, RuntimeError)):
            await m.enqueue("rr/kick")       # kicks the reconnect loop
        for _ in range(100):
            if m.reconnects and svc2.subs_applied >= 2:
                break
            await asyncio.sleep(0.05)
        assert sum(reseeds) == 1             # replayed exactly once
        assert svc2.subs_applied == 2        # the live set, no extras
        got = await m.subscribers_async("rr/y")
        assert set(got.subscriptions) == {"rc1", "rc2"}
    finally:
        await m.close()
        await svc2.close()


async def test_reconnect_backoff_retries_while_quiet(tmp_path):
    """The reconnect loop keeps retrying under capped exponential
    backoff while traffic is quiet — the old behavior gave up after one
    OSError and waited for the next enqueue, so a silent broker stayed
    disconnected as long as it stayed silent."""
    path = str(tmp_path / "m.sock")
    svc = MatcherService(path)
    await svc.start()
    m = ServiceMatcher(path)
    m.RECONNECT_BACKOFF_INITIAL = 0.02
    m.RECONNECT_BACKOFF_MAX = 0.1
    await m.connect()
    await m.subscribers_async("warm/x")      # connection fully accepted
    await svc.close()                        # service gone
    with pytest.raises((ConnectionError, RuntimeError)):
        await m.enqueue("q/x")               # ONE kick, then silence
    await asyncio.sleep(0.3)                 # loop retries on its own
    assert m.reconnect_attempts >= 2, m.reconnect_attempts
    svc2 = MatcherService(path)
    await svc2.start()
    try:
        for _ in range(100):                 # no further enqueues: the
            if m.reconnects:                 # loop alone reconnects
                break
            await asyncio.sleep(0.05)
        assert m.reconnects == 1
        assert m._writer is not None and not m._writer.is_closing()
    finally:
        await m.close()
        await svc2.close()
