"""Regression tests for broker accounting/housekeeping fixes:

- packets_received counts parsed packets, not TCP read chunks;
- bytes pipelined after CONNECT in the same segment are processed;
- retained-message expiry runs off a min-expiry heap with lazy
  revalidation (no full-tree rescan per tick);
- fire-and-forget broker tasks log their failures.
"""

import asyncio
import time

import pytest

from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities
from maxmq_tpu.broker.server import _FanOut
from maxmq_tpu.protocol.codec import FixedHeader, PacketType as PT
from maxmq_tpu.protocol.packets import Packet

from test_broker_system import running_broker


def _connect_bytes(client_id: str) -> bytes:
    return Packet(fixed=FixedHeader(type=PT.CONNECT), protocol_version=4,
                  clean_start=True, client_id=client_id).encode()


async def test_packets_received_counts_packets_not_chunks():
    """A CONNECT fragmented into 1-byte segments is ONE received packet
    (the reference counts per packet too, v2/system/system.go)."""
    async with running_broker() as broker:
        raw = _connect_bytes("frag")
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", broker.test_port)
        for b in raw:
            writer.write(bytes([b]))
            await writer.drain()
        await asyncio.wait_for(reader.readexactly(4), 5)   # CONNACK
        assert broker.info.packets_received == 1
        writer.close()


async def test_pipelined_packets_after_connect_processed():
    """A client may pipeline packets behind CONNECT in one TCP segment;
    the leftover bytes must reach the read loop, not be discarded."""
    async with running_broker() as broker:
        ping = Packet(fixed=FixedHeader(type=PT.PINGREQ),
                      protocol_version=4).encode()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", broker.test_port)
        writer.write(_connect_bytes("pipe") + ping)
        await writer.drain()
        data = await asyncio.wait_for(reader.readexactly(6), 5)
        assert data[0] >> 4 == PT.CONNACK
        assert data[4] >> 4 == PT.PINGRESP
        assert broker.info.packets_received == 2
        writer.close()


def _retained(topic: str, payload: bytes, created: float,
              expiry: int | None = None) -> Packet:
    p = Packet(fixed=FixedHeader(type=PT.PUBLISH, retain=True),
               topic=topic, payload=payload, created=created)
    if expiry is not None:
        p.properties.message_expiry = expiry
    return p


def test_retained_expiry_heap_expires_and_revalidates():
    b = Broker(BrokerOptions(capabilities=Capabilities(
        maximum_message_expiry_interval=60)))
    now = time.time()

    # an already-expired message is cleared on the next sweep
    b.retain_message(None, _retained("room/a", b"v1", created=now - 120))
    assert len(b._retained_expiry) == 1
    b._check_expired_retained(now)
    assert b.topics.retained_get("room/a") is None

    # replacement invalidates the stale heap entry (lazy revalidation)
    b.retain_message(None, _retained("room/b", b"v1", created=now - 120))
    b.retain_message(None, _retained("room/b", b"v2", created=now))
    b._check_expired_retained(now)
    assert b.topics.retained_get("room/b").payload == b"v2"
    # ... and the replacement's own entry fires when it is due
    b._check_expired_retained(now + 120)
    assert b.topics.retained_get("room/b") is None

    # per-message expiry beats the capability maximum
    b.retain_message(None, _retained("room/c", b"v1", created=now - 5,
                                     expiry=2))
    b._check_expired_retained(now)
    assert b.topics.retained_get("room/c") is None


def test_retained_expiry_skips_sys_and_disabled():
    b = Broker(BrokerOptions(capabilities=Capabilities(
        maximum_message_expiry_interval=60)))
    sys_p = _retained("$SYS/broker/load", b"s", created=0.0)
    b.topics.retain(sys_p)
    b._note_retained_expiry(sys_p)
    assert not b._retained_expiry          # broker-owned: never indexed

    b2 = Broker(BrokerOptions(capabilities=Capabilities(
        maximum_message_expiry_interval=0)))
    b2.retain_message(None, _retained("x", b"v", created=0.0))
    assert not b2._retained_expiry         # expiry disabled: no index
    b2._check_expired_retained(time.time())
    assert b2.topics.retained_get("x") is not None


class _WireSink:
    """Stub client: captures what _send_fast_qos0 enqueues."""

    def __init__(self, version: int):
        from maxmq_tpu.broker.client import ClientProperties
        self.properties = ClientProperties(protocol_version=version)
        self.wires: list[bytes] = []

    def send_wire(self, wire: bytes) -> bool:
        self.wires.append(wire)
        return True


def test_fast_qos0_wire_matches_full_encoder():
    """The direct wire build in _send_fast_qos0 must stay byte-identical
    to the codec's own encoding of the delivery form — this pins the
    inlined fast path to the codec against future encoding changes."""
    b = Broker(BrokerOptions())
    for version in (3, 4, 5):
        for topic, payload in [("a/b", b"x" * 64), ("t", b""),
                               ("deep/l1/l2/l3", b"\x00\xff" * 40),
                               ("unicodé/世界", b"p")]:
            pkt = Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=1,
                                           retain=True, dup=True),
                         topic=topic, payload=payload, packet_id=9)
            want = b._delivery_form(pkt, version).encode()
            sink = _WireSink(version)
            b._send_fast_qos0(sink, pkt, _FanOut(b, pkt))
            assert sink.wires == [want], (version, topic)


class _CapturingLogger:
    def __init__(self):
        self.errors = []

    def with_prefix(self, prefix):
        return self

    def error(self, msg, **fields):
        self.errors.append((msg, fields))


async def test_spawn_logs_background_failures():
    log = _CapturingLogger()
    b = Broker(BrokerOptions(logger=log))
    b.loop = asyncio.get_running_loop()

    async def boom():
        raise RuntimeError("kaput")

    t = b._spawn(boom(), "test-task")
    with pytest.raises(RuntimeError):
        await t
    await asyncio.sleep(0)
    assert log.errors
    assert log.errors[0][1]["task"] == "test-task"
    assert "kaput" in log.errors[0][1]["error"]


async def test_socket_listener_serves_prebound_socket():
    # the bring-your-own-listener analog (reference listeners/net.go):
    # an externally bound socket handed to the broker just accepts
    import socket

    from maxmq_tpu.broker import (Broker, BrokerOptions, Capabilities,
                                  SocketListener)
    from maxmq_tpu.hooks import AllowHook
    from maxmq_tpu.mqtt_client import MQTTClient

    sock = socket.socket()
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    b = Broker(BrokerOptions(capabilities=Capabilities(
        sys_topic_interval=0)))
    b.add_hook(AllowHook())
    b.add_listener(SocketListener("byo", sock))
    await b.serve()
    try:
        c = MQTTClient("byo-c")
        await c.connect("127.0.0.1", port)
        await c.subscribe("byo/#")
        await c.publish("byo/x", b"via-prebound")
        m = await c.next_message(5)
        assert m.payload == b"via-prebound"
        await c.disconnect()
    finally:
        await b.close()


class _TrieMatcher:
    """Minimal pluggable matcher: trie semantics behind the async
    matcher surface, so tests exercise the publish pipeline."""

    def __init__(self, index):
        self.index = index

    async def subscribers_async(self, topic):
        return self.index.subscribers(topic)


async def test_publish_pipeline_survives_raising_hook():
    """A hook raising during fan-out must cost that one publish, not the
    pipeline consumer — a dead consumer wedges every matcher-mode
    publisher behind a full queue (review finding, round 3)."""
    from test_broker_system import connect, running_broker

    from maxmq_tpu.hooks.base import Hook

    class Boom(Hook):
        def __init__(self):
            self.fired = 0

        def on_published(self, client, packet):
            self.fired += 1
            if self.fired == 1:
                raise RuntimeError("hook kaput")

    async with running_broker() as broker:
        boom = broker.add_hook(Boom())
        broker.attach_matcher(_TrieMatcher(broker.topics))
        sub = await connect(broker, "pl-sub")
        await sub.subscribe(("t/#", 0))
        pub = await connect(broker, "pl-pub")
        await pub.publish("t/1", b"a")        # hook raises on this one
        await pub.publish("t/2", b"b")        # must still deliver
        m1 = await sub.next_message(timeout=5)
        m2 = await sub.next_message(timeout=5)
        assert {m1.topic, m2.topic} == {"t/1", "t/2"}
        assert boom.fired == 2
        assert not broker._pub_consumer.done()
        await sub.disconnect()
        await pub.disconnect()


async def test_publish_pipeline_resets_on_close():
    """close() must reset the pipeline so a re-serve()d broker lazily
    recreates the consumer (review finding, round 3)."""
    from test_broker_system import connect, running_broker

    async with running_broker() as broker:
        broker.attach_matcher(_TrieMatcher(broker.topics))
        sub = await connect(broker, "rs-sub")
        await sub.subscribe(("r/#", 0))
        pub = await connect(broker, "rs-pub")
        await pub.publish("r/1", b"x")
        m = await sub.next_message(timeout=5)
        assert m.topic == "r/1"
        assert broker._pub_consumer is not None
    assert broker._pub_consumer is None and broker._pub_queue is None


class _ScrambledMatcher:
    """Matcher whose results resolve in RANDOM order: the publish
    pipeline must still fan out in arrival order [MQTT-4.6.0]."""

    def __init__(self, index):
        self.index = index
        import random
        self._rng = random.Random(3)

    async def subscribers_async(self, topic):
        import asyncio
        await asyncio.sleep(self._rng.random() * 0.02)
        return self.index.subscribers(topic)


async def test_publish_pipeline_preserves_publish_order():
    from test_broker_system import connect, running_broker

    async with running_broker() as broker:
        broker.attach_matcher(_ScrambledMatcher(broker.topics))
        sub = await connect(broker, "ord-sub")
        await sub.subscribe(("seq/#", 0))
        pub = await connect(broker, "ord-pub")
        n = 40
        for i in range(n):
            await pub.publish(f"seq/{i}", str(i).encode())
        got = [await sub.next_message(timeout=10) for _ in range(n)]
        assert [int(m.payload) for m in got] == list(range(n)), \
            "deliveries out of publish order"
        await sub.disconnect()
        await pub.disconnect()


async def test_tls_listener_roundtrip(tmp_path):
    """TLS TCP listener: a client over ssl does a full QoS0 roundtrip
    (parity: vendor/.../v2/listeners/tcp.go TLS config path)."""
    import ssl
    import subprocess

    from test_broker_system import running_broker

    from maxmq_tpu.broker import TCPListener
    from maxmq_tpu.mqtt_client import MQTTClient

    key, crt = tmp_path / "k.pem", tmp_path / "c.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(crt), "-days", "1",
         "-subj", "/CN=localhost"],
        check=True, capture_output=True)
    server_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    server_ctx.load_cert_chain(str(crt), str(key))

    async with running_broker() as broker:
        lst = broker.add_listener(
            TCPListener("tls1", "127.0.0.1:0", tls=server_ctx))
        await lst.serve(broker._establish)
        port = lst._server.sockets[0].getsockname()[1]

        client_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        client_ctx.check_hostname = False
        client_ctx.verify_mode = ssl.CERT_NONE
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", port, ssl=client_ctx)
        c = MQTTClient(client_id="tls-c")
        await c.connect(None, None, reader=reader, writer=writer)
        await c.subscribe(("tls/#", 0))
        await c.publish("tls/x", b"secured")
        m = await c.next_message(timeout=10)
        assert m.payload == b"secured"
        await c.disconnect()


def test_retained_expiry_heap_bounded_under_republish():
    """A retained topic republished many times must not grow the expiry
    heap by one stale entry per publish (soak-found leak): lazy
    deletion + bounded rebuild keep the heap O(live retained topics)."""
    b = Broker(BrokerOptions(capabilities=Capabilities(
        maximum_message_expiry_interval=3600)))

    class _C:
        id = "rp"
        inline = True

    for i in range(5000):
        p = Packet(fixed=FixedHeader(type=PT.PUBLISH, retain=True),
                   topic=f"rp/{i % 8}", payload=b"x")
        p.created = 1000.0 + i
        b.retain_message(_C(), p)
    assert len(b._retained_expiry) <= 64, len(b._retained_expiry)
    assert len(b._retained_due) == 8
    # clearing a retained topic drops its due entry
    clear = Packet(fixed=FixedHeader(type=PT.PUBLISH, retain=True),
                   topic="rp/0", payload=b"")
    clear.created = 9999.0
    b.retain_message(_C(), clear)
    assert "rp/0" not in b._retained_due
    # expiry still fires off the compacted heap
    b._check_expired_retained(now=1000.0 + 5000 + 3600 + 1)
    assert not b._retained_due


async def test_restore_path_prewarms_decode_anchors(tmp_path):
    """A broker restored with a large subscription set must run
    prewarm_decode_bases at the boot quiescent point: the restore path
    used to call only refresh(), deferring anchor population to the
    first background rotation — i.e. paying the ramp across the first
    few hundred thousand cold publishes (ADVICE r5 #1)."""
    from maxmq_tpu.hooks import AllowHook
    from maxmq_tpu.hooks.storage import (SQLiteStore, StorageHook,
                                         SubscriptionRecord)
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.sig import SigEngine

    path = str(tmp_path / "prewarm.db")
    store = SQLiteStore(path)
    # >= 10K subscriptions incl. one fat '#' bucket (chain-eligible:
    # well past the decode's min-base bar), written straight into the
    # store — the restore path reads records, not live clients
    for i in range(200):
        store.put("subscriptions", f"fat{i}|pw/dev/#",
                  SubscriptionRecord(client_id=f"fat{i}",
                                     filter="pw/dev/#", qos=1).to_json())
    for i in range(9800):
        store.put("subscriptions", f"c{i}|pw/{i}/x",
                  SubscriptionRecord(client_id=f"c{i}",
                                     filter=f"pw/{i}/x", qos=0).to_json())
    store.close()

    b = Broker(BrokerOptions(capabilities=Capabilities(
        sys_topic_interval=0)))
    b.add_hook(AllowHook())
    b.add_hook(StorageHook(SQLiteStore(path)))
    engine = SigEngine(b.topics, auto_refresh=False)
    engine.emit_intents = True     # production shape (ADR 007)
    calls: list[int] = []
    orig_prewarm = engine.prewarm_decode_bases

    def counting_prewarm(*a, **k):
        calls.append(1)
        return orig_prewarm(*a, **k)

    engine.prewarm_decode_bases = counting_prewarm
    b.attach_matcher(MicroBatcher(engine))
    await b.serve()
    try:
        # prewarm ran inside serve(), i.e. BEFORE any publish dispatch
        assert calls, "restore path never ran prewarm_decode_bases"
        # and against the restored corpus, not the boot-empty tables
        assert b.topics.subscription_count >= 10_000
        assert engine.tables.version == b.topics.sub_version
        from maxmq_tpu.native import decode_module
        mod = decode_module()
        if mod is not None and hasattr(mod, "_slot_map_stats"):
            nd = engine.tables.__dict__.get("_native_decode")
            assert nd, "native decode never engaged for the prewarm"
            rows_mapped, entries = mod._slot_map_stats(nd[1])
            assert rows_mapped >= 1, "no anchor slot maps populated"
            assert entries >= 200   # the fat row's plain entries
    finally:
        await b.close()
