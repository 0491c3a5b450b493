"""Black-box system tests: a real broker on a real TCP socket, exercised by
the in-repo MQTT client. Mirrors the reference's paho system suite
(tests/system/mqtt_test.go): connect/disconnect, keepalive, wildcard
subscribe with granted QoS, unsubscribe, QoS0 roundtrip, QoS1/QoS2
offline-delivery, plus retained/will/takeover/shared-subscription scenarios.
"""

import asyncio
from contextlib import asynccontextmanager

import pytest

from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities, TCPListener
from maxmq_tpu.hooks import AllowHook
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.protocol import Will


@asynccontextmanager
async def running_broker(**caps):
    caps.setdefault("sys_topic_interval", 0)
    b = Broker(BrokerOptions(capabilities=Capabilities(**caps)))
    b.add_hook(AllowHook())
    listener = b.add_listener(TCPListener("t1", "127.0.0.1:0"))
    await b.serve()
    b.test_port = listener._server.sockets[0].getsockname()[1]
    try:
        yield b
    finally:
        await b.close()


async def connect(broker, client_id="", version=4, **kw) -> MQTTClient:
    c = MQTTClient(client_id=client_id, version=version, **kw)
    await c.connect("127.0.0.1", broker.test_port)
    return c


async def test_connect_disconnect():
    async with running_broker() as broker:
        c = await connect(broker, "c1")
        assert c.connack.reason_code == 0
        assert c.connack.session_present is False
        assert broker.info.clients_connected == 1
        await c.disconnect()
        await asyncio.sleep(0.05)
        assert broker.info.clients_connected == 0


async def test_keepalive_ping():
    async with running_broker() as broker:
        c = await connect(broker, "c1", keepalive=2)
        for _ in range(3):
            await c.ping()
            await asyncio.sleep(0.05)
        await c.disconnect()


async def test_keepalive_timeout_drops_client():
    async with running_broker(keepalive_grace=0.2) as broker:
        c = await connect(broker, "c1", keepalive=1)
        await c.wait_closed(timeout=5)
        await asyncio.sleep(0.05)
        assert broker.info.clients_connected == 0


async def test_keepalive_clamped_to_maximum():
    """Operator keepalive limit: clamp + v5 ServerKeepAlive [MQTT-3.1.2-21]."""
    async with running_broker(maximum_keepalive=5) as broker:
        c = await connect(broker, "c1", version=5, keepalive=60)
        assert c.connack.properties.server_keep_alive == 5
        assert broker.clients.get("c1").keepalive == 5
        await c.disconnect()
        # keepalive 0 (never drop) is also subject to the operator limit
        c2 = await connect(broker, "c2", version=5, keepalive=0)
        assert c2.connack.properties.server_keep_alive == 5
        await c2.disconnect()


async def test_subscribe_wildcards_granted_qos():
    async with running_broker() as broker:
        c = await connect(broker, "c1")
        granted = await c.subscribe(("sensor/#", 0), ("data/+/raw", 1),
                                    ("exact/topic", 2))
        assert granted == [0, 1, 2]
        assert broker.info.subscriptions == 3


async def test_subscribe_invalid_filter_rejected():
    async with running_broker() as broker:
        c = await connect(broker, "c1", version=5)
        granted = await c.subscribe("bad/#/filter")
        assert granted == [0x8F]


async def test_unsubscribe():
    async with running_broker() as broker:
        c = await connect(broker, "c1")
        await c.subscribe("a/b")
        await c.unsubscribe("a/b")
        await c.publish("a/b", b"after-unsub")
        with pytest.raises(asyncio.TimeoutError):
            await c.next_message(timeout=0.2)


async def test_qos0_roundtrip():
    async with running_broker() as broker:
        s = await connect(broker, "sub")
        p = await connect(broker, "pub")
        await s.subscribe("room/+/temp")
        await p.publish("room/kitchen/temp", b"21.5")
        msg = await s.next_message()
        assert (msg.topic, msg.payload, msg.qos) == \
            ("room/kitchen/temp", b"21.5", 0)


@pytest.mark.parametrize("qos", [1, 2])
async def test_offline_delivery(qos):
    """Persistent session disconnects; messages published meanwhile are
    delivered on reconnect (the reference's headline QoS1/QoS2 scenario)."""
    async with running_broker() as broker:
        s = await connect(broker, "subber", clean_start=False)
        await s.subscribe(("queue/data", qos))
        await s.close()  # network drop, not DISCONNECT: session persists
        await asyncio.sleep(0.05)

        p = await connect(broker, "pubber")
        await p.publish("queue/data", b"while-away", qos=qos)
        await p.disconnect()

        s2 = MQTTClient(client_id="subber", version=4, clean_start=False)
        await s2.connect("127.0.0.1", broker.test_port)
        assert s2.connack.session_present is True
        msg = await s2.next_message()
        assert msg.payload == b"while-away"
        assert msg.qos == qos
        await s2.disconnect()


async def test_qos2_exactly_once_dedup():
    async with running_broker() as broker:
        s = await connect(broker, "sub")
        p = await connect(broker, "pub")
        await s.subscribe(("once/t", 2))
        for i in range(3):
            await p.publish("once/t", f"m{i}".encode(), qos=2)
        got = [await s.next_message() for _ in range(3)]
        assert [m.payload for m in got] == [b"m0", b"m1", b"m2"]
        with pytest.raises(asyncio.TimeoutError):
            await s.next_message(timeout=0.2)


async def test_retained_message_delivery():
    async with running_broker() as broker:
        p = await connect(broker, "pub")
        await p.publish("config/node1", b"v1", retain=True)
        await asyncio.sleep(0.05)
        s = await connect(broker, "sub")
        await s.subscribe("config/+")
        msg = await s.next_message()
        assert msg.payload == b"v1"
        assert msg.retain is True
        # clearing: empty retained payload
        await p.publish("config/node1", b"", retain=True)
        await asyncio.sleep(0.05)
        s2 = await connect(broker, "sub2")
        await s2.subscribe("config/+")
        with pytest.raises(asyncio.TimeoutError):
            await s2.next_message(timeout=0.2)


async def test_will_on_abnormal_disconnect():
    async with running_broker() as broker:
        s = await connect(broker, "watcher")
        await s.subscribe("wills/+")
        w = await connect(broker, "doomed",
                          will=Will(topic="wills/doomed", payload=b"gone"))
        await w.close()  # abrupt close -> will fires
        msg = await s.next_message()
        assert (msg.topic, msg.payload) == ("wills/doomed", b"gone")


async def test_no_will_on_clean_disconnect():
    async with running_broker() as broker:
        s = await connect(broker, "watcher")
        await s.subscribe("wills/+")
        w = await connect(broker, "polite",
                          will=Will(topic="wills/polite", payload=b"gone"))
        await w.disconnect()
        with pytest.raises(asyncio.TimeoutError):
            await s.next_message(timeout=0.2)


async def test_session_takeover():
    async with running_broker() as broker:
        c1 = await connect(broker, "same-id", version=5)
        c2 = await connect(broker, "same-id", version=5)
        await c1.wait_closed()
        assert c1.disconnect_packet is not None
        assert c1.disconnect_packet.reason_code == 0x8E  # session taken over
        await c2.ping()  # new connection is live
        await c2.disconnect()


async def test_shared_subscription_round_robin():
    async with running_broker() as broker:
        a = await connect(broker, "worker-a", version=5)
        b = await connect(broker, "worker-b", version=5)
        p = await connect(broker, "pub", version=5)
        await a.subscribe("$share/grp/jobs")
        await b.subscribe("$share/grp/jobs")
        for i in range(4):
            await p.publish("jobs", f"j{i}".encode())
        await asyncio.sleep(0.1)
        got_a, got_b = a.messages.qsize(), b.messages.qsize()
        assert got_a + got_b == 4
        assert got_a == 2 and got_b == 2  # round-robin fairness


async def test_dollar_sys_subscription():
    async with running_broker() as broker:
        c = await connect(broker, "c1")
        await c.subscribe("$SYS/#")
        broker.publish_sys_topics()
        msg = await c.next_message()
        assert msg.topic.startswith("$SYS/")


async def test_clients_cannot_publish_dollar_topics():
    async with running_broker() as broker:
        watcher = await connect(broker, "w")
        await watcher.subscribe("$SYS/#")
        c = await connect(broker, "c1")
        await c.publish("$SYS/broker/version", b"fake")
        with pytest.raises(asyncio.TimeoutError):
            await watcher.next_message(timeout=0.2)


async def test_no_local_v5():
    async with running_broker() as broker:
        c = await connect(broker, "c1", version=5)
        await c.subscribe(("loop/t", 0), no_local=True)
        await c.publish("loop/t", b"self")
        with pytest.raises(asyncio.TimeoutError):
            await c.next_message(timeout=0.2)


async def test_v5_clean_start_discards_session():
    async with running_broker() as broker:
        c = await connect(broker, "cs", version=5, clean_start=False,
                          session_expiry=300)
        await c.subscribe("keep/me")
        await c.close()
        await asyncio.sleep(0.05)
        c2 = MQTTClient(client_id="cs", version=5, clean_start=True)
        await c2.connect("127.0.0.1", broker.test_port)
        assert c2.connack.session_present is False
        await c2.disconnect()


async def test_second_connect_is_protocol_violation():
    async with running_broker() as broker:
        c = await connect(broker, "c1")
        from maxmq_tpu.protocol import FixedHeader, Packet, PacketType as PT
        dup = Packet(fixed=FixedHeader(type=PT.CONNECT), protocol_version=4,
                     client_id="c1", clean_start=True)
        c.writer.write(dup.encode())
        await c.writer.drain()
        await c.wait_closed()  # broker must drop the connection


async def test_inline_publish_api():
    async with running_broker() as broker:
        c = await connect(broker, "c1")
        await c.subscribe("inline/+")
        await broker.publish("inline/x", b"from-server", retain=False)
        msg = await c.next_message()
        assert msg.payload == b"from-server"


async def test_retained_qos_downgrade_and_sub_qos():
    async with running_broker() as broker:
        p = await connect(broker, "pub")
        await p.publish("r/t", b"keep", qos=1, retain=True)
        await asyncio.sleep(0.05)
        s = await connect(broker, "sub")
        await s.subscribe(("r/t", 0))  # subscription qos caps delivery
        msg = await s.next_message()
        assert msg.qos == 0 and msg.payload == b"keep"


async def test_broker_with_bare_engine_attached():
    """Full path: PUBLISH over TCP -> an engine attached with no batcher
    (no ``enqueue``: the broker's ``subscribers_async`` road) -> fan-out."""
    from maxmq_tpu.matching.sig import SigEngine
    async with running_broker() as broker:
        engine = SigEngine(broker.topics)
        broker.attach_matcher(engine)
        s = await connect(broker, "sub", version=5)
        await s.subscribe(("eng/+/path", 1), ("$share/g/eng/shared", 0))
        p = await connect(broker, "pub")
        await p.publish("eng/hot/path", b"via-engine", qos=1)
        msg = await s.next_message()
        assert (msg.topic, msg.payload, msg.qos) == ("eng/hot/path", b"via-engine", 1)
        await p.publish("eng/shared", b"shared-via-engine")
        msg = await s.next_message()
        assert msg.payload == b"shared-via-engine"
        # subscription mutations picked up by auto-refresh
        await s.unsubscribe("eng/+/path")
        await p.publish("eng/hot/path", b"after-unsub")
        with pytest.raises(asyncio.TimeoutError):
            await s.next_message(timeout=0.3)
        assert engine.matches == 3      # every publish asked the engine


async def test_broker_with_sig_matcher_intents():
    """Full path through DeliveryIntents (ADR 007): PUBLISH over TCP ->
    sig match -> native decode emits intents -> broker fans out from the
    flat entries. Covers plain QoS1, $share exactly-once across two
    group members, NoLocal, and a select_subscribers hook forcing the
    to_set() materialization path."""
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.sig import SigEngine
    from maxmq_tpu.native import decode_module
    mod = decode_module()
    if mod is None or not hasattr(mod, "DeliveryIntents"):
        pytest.skip("maxmq_decode extension unavailable")
    async with running_broker() as broker:
        eng = SigEngine(broker.topics)
        eng.emit_intents = True
        eng.route_small = False   # force the device/intents path
        broker.attach_matcher(MicroBatcher(eng, window_us=0))
        s = await connect(broker, "sub", version=5)
        await s.subscribe(("ity/+/path", 1))
        g1 = await connect(broker, "g1", version=5)
        await g1.subscribe(("$share/g/ity/shared", 0))
        g2 = await connect(broker, "g2", version=5)
        await g2.subscribe(("$share/g/ity/shared", 0))
        p = await connect(broker, "pub")
        await p.publish("ity/hot/path", b"via-intents", qos=1)
        msg = await s.next_message()
        assert (msg.topic, msg.payload, msg.qos) == \
            ("ity/hot/path", b"via-intents", 1)
        # $share: exactly one of the two group members per publish
        for i in range(6):
            await p.publish("ity/shared", f"s{i}".encode())
        deadline = asyncio.get_running_loop().time() + 15
        while (g1.messages.qsize() + g2.messages.qsize()) < 6:
            assert asyncio.get_running_loop().time() < deadline, (
                f"shared fan-out delivered "
                f"{g1.messages.qsize() + g2.messages.qsize()}, want 6")
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.2)          # no duplicates trickling in
        assert g1.messages.qsize() + g2.messages.qsize() == 6
        # NoLocal: publisher subscribed no_local must not self-receive
        nl = await connect(broker, "nl", version=5)
        await nl.subscribe(("ity/nl", 0), no_local=True)
        await nl.publish("ity/nl", b"self")
        with pytest.raises(asyncio.TimeoutError):
            await nl.next_message(timeout=0.3)
        # a select_subscribers hook flips the fan-out to to_set()
        from maxmq_tpu.hooks.base import Hook

        class DropAll(Hook):
            id = "drop-all-sel"

            def on_select_subscribers(self, subscribers, packet):
                subscribers.subscriptions.clear()
                return subscribers
        broker.add_hook(DropAll())
        await p.publish("ity/hot/path", b"suppressed", qos=1)
        with pytest.raises(asyncio.TimeoutError):
            await s.next_message(timeout=0.3)


async def test_send_quota_holds_and_releases():
    """v5 receive-maximum flow control: excess QoS1 fan-out parks on the
    held queue and drains as acks return quota."""
    async with running_broker() as broker:
        s = MQTTClient(client_id="slow", version=5)
        s.session_expiry = 0
        await s.connect("127.0.0.1", broker.test_port)
        # advertise a tiny receive maximum by hand-crafting the CONNECT:
        # easier path — reach into the session and shrink the send quota
        sess = broker.clients.get("slow")
        sess.inflight.maximum_send = 1
        sess.inflight.send_quota = 1
        await s.subscribe(("flow/t", 1))
        p = await connect(broker, "pub")
        for i in range(3):
            await p.publish("flow/t", f"m{i}".encode(), qos=1)
        got = [await s.next_message(timeout=3) for _ in range(3)]
        assert sorted(m.payload for m in got) == [b"m0", b"m1", b"m2"]
        assert not sess.held_pids


async def test_select_subscribers_hook_at_scale():
    """Hook-present fan-out at scale (the round-4 verdict's weak spot:
    any installed on_select_subscribers fell back to the merged-set
    rate). A modifying selection hook must ride intents ->
    select_set() with ALIASED Subscription records — a C-side dict
    materialization (cached per row set once it re-hits), never a
    per-publish deep copy — while a declared record-mutator still gets
    full isolation."""
    from maxmq_tpu.hooks.base import Hook
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.sig import SigEngine
    from maxmq_tpu.protocol.packets import Subscription as Sub

    async with running_broker() as broker:
        for i in range(20_000):
            broker.topics.subscribe(
                f"synth-{i}", Sub(filter=f"scale/x{i % 4000}/t", qos=0))
        for i in range(8):
            broker.topics.subscribe(f"wild-{i}", Sub(filter="scale/+/t"))
        s = await connect(broker, "real-sub")
        await s.subscribe(("scale/+/t", 0))

        engine = SigEngine(broker.topics)
        engine.emit_intents = True
        engine.route_small = False         # force the device decode path
        broker.attach_matcher(MicroBatcher(engine, window_us=100,
                                           max_batch=64))
        wild0_recs: list = []          # strong refs: id() stays valid
        sizes: list[int] = []

        class DropWild1(Hook):
            id = "drop-wild1"

            def on_select_subscribers(self, subscribers, packet):
                rec = subscribers.subscriptions.get("wild-0")
                if rec is not None:
                    wild0_recs.append(rec)
                subscribers.subscriptions.pop("wild-1", None)
                sizes.append(len(subscribers.subscriptions))
                return subscribers

        broker.add_hook(DropWild1())
        p = await connect(broker, "pub")
        n_pub = 100
        for i in range(n_pub):
            await p.publish(f"scale/x{i}/t", b"m", qos=0)
        got = [await s.next_message(timeout=10) for _ in range(n_pub)]
        assert len(got) == n_pub           # real-sub never dropped
        assert len(sizes) == n_pub         # hook ran on every publish
        # every result: 5 synth matches + 8 wild + real-sub, minus the
        # dropped wild-1
        assert sizes == [5 + 8 + 1 - 1] * n_pub, sizes[:5]
        # the fast-tier contract: records are ALIASED from the matcher's
        # caches — one stored record observed across all 100 publishes.
        # A per-publish deep copy would yield 100 distinct objects
        # (strong refs retained above, so identity comparison is sound).
        assert all(r is wild0_recs[0] for r in wild0_recs), \
            "records were copied per publish"

        # opt-in record-mutator tier: declared hooks get isolation
        mut_recs: list = []            # strong refs: id() stays valid

        class MutateWild0(Hook):
            id = "mutate-wild0"
            select_subscribers_mutates_records = True

            def on_select_subscribers(self, subscribers, packet):
                rec = subscribers.subscriptions.get("wild-0")
                if rec is not None:
                    mut_recs.append(rec)
                    rec.qos = 2            # must not leak to the caches
                return subscribers

        broker.add_hook(MutateWild0())
        for i in range(3):
            await p.publish("scale/x1/t", b"m2", qos=0)
            await s.next_message(timeout=10)
        assert len(mut_recs) == 3
        assert len({id(r) for r in mut_recs}) == 3, \
            "mutator saw a shared record"
        stored = broker.topics.subscribers("scale/x1/t")
        assert stored.subscriptions["wild-0"].qos == 0, \
            "record mutation leaked into the index"
