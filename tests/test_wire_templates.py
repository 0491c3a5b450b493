"""ADR-019 zero-copy fan-out differential suite.

The one invariant that makes shared wire templates safe is byte
identity: for every (protocol version, QoS, v5 feature set) a patched
template delivery must put EXACTLY the bytes on the wire that the slow
path (``_build_outbound(...).encode()``) would have. This file holds
that matrix — v3.1.1/v5 x QoS 0/1/2 x {subscription ids, topic alias,
retain-as-published, max-packet-size, encode/sent hook overrides} —
plus the satellite ledgers the template path must keep exact:

* byte accounting: a queued wire entry's charged size equals its
  socket bytes, and ``_estimate_wire`` covers the v5 property shapes
  on the residual Packet paths (ADR 012 / satellite 2);
* drop parity: fast/template-path refusals feed the SAME ledgers as
  the slow path — drops_by_reason, budget_drops, qos_drops, and the
  drain-stage error counter (satellite 4);
* path selection: hook overrides and instance-patched send seams force
  the per-subscriber copy+encode slow path (satellite 3).

Deliveries are captured at the outbound queue (an instance-level
``put_nowait`` intercept — deliberately NOT ``client.send``/
``send_buffers``, which _template_eligible treats as the slow-path
seam), so each case asserts the queue entry's exact type, bytes and
charged size.
"""

import asyncio
import copy
import time

import pytest

from test_broker_system import connect, running_broker

from maxmq_tpu import faults
from maxmq_tpu.broker.client import _estimate_wire
from maxmq_tpu.hooks import Hook
from maxmq_tpu.protocol.codec import FixedHeader
from maxmq_tpu.protocol.codec import PacketType as PT
from maxmq_tpu.protocol.packets import Packet, Subscription
from maxmq_tpu.protocol.properties import Properties


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


async def poll(predicate, timeout: float = 5.0, what: str = ""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"condition not reached in {timeout}s: {what}")


def stall_writer(client_id: str, delay_s: float = 30.0) -> None:
    faults.arm(f"{faults.CLIENT_WRITE}#{client_id}", "hang",
               count=-1, delay_s=delay_s)


def _rich_props() -> Properties:
    """A property block with content on BOTH sides of the template's
    splice point: prefix (payload_format..correlation_data) and the
    user-property suffix the per-subscriber segment sits between."""
    return Properties(payload_format=1, content_type="application/json",
                      correlation_data=b"corr-1234",
                      user_properties=[("origin", "matrix"),
                                       ("pad", "v" * 40)])


def _pub(topic="sensor/kitchen/temp", payload=b"x" * 48, qos=0,
         retain=False, props: Properties | None = None) -> Packet:
    p = Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=qos, retain=retain),
               protocol_version=5, topic=topic, payload=payload)
    if props is not None:
        p.properties = props
    return p


async def _deliver(broker, cl, sub, packet, expect: str) -> bytes:
    """Run ONE delivery through _publish_to_client with the outbound
    queue intercepted; assert the captured entry took the ``expect``
    path ("bytes" | "tuple" | "packet") and is byte-identical to the
    slow path's ``_build_outbound(...).encode()``. Returns the
    reference wire."""
    # the reference consumes no client state: alias assignments are
    # rolled back so the template path sees the same progression
    aliases = copy.deepcopy(cl.aliases)
    ref = broker._build_outbound(cl, sub, packet)
    cl.aliases = aliases
    before = {p.packet_id for p in cl.inflight.all()}
    rec: list = []
    cl.outbound.put_nowait = lambda item, size=0: rec.append((item, size))
    try:
        broker._publish_to_client(cl, sub, packet, shared=False)
    finally:
        del cl.outbound.put_nowait
    assert len(rec) == 1, f"expected one delivery, saw {len(rec)}"
    item, size = rec[0]
    if ref.fixed.qos > 0:
        new = [p.packet_id for p in cl.inflight.all()
               if p.packet_id not in before]
        assert len(new) == 1, "QoS>0 delivery must register one inflight"
        ref.packet_id = new[0]
    refw = ref.encode()
    kind = {bytes: "bytes", tuple: "tuple"}.get(type(item), "packet")
    assert kind == expect, f"took {kind} path, expected {expect}"
    if kind == "tuple":
        assert b"".join(item) == refw
        assert size == len(refw) == sum(len(b) for b in item)
    elif kind == "bytes":
        assert item == refw
        assert size == len(refw)
    else:
        assert item.encode() == refw
    return refw


# -- differential matrix: template bytes == slow-path bytes ------------


async def test_differential_matrix_v4():
    """v3.1.1 subscribers: QoS flags + packet id are the only frame
    variation; v5 properties of the inbound publish must vanish."""
    async with running_broker() as broker:
        c = await connect(broker, "v4sub", version=4)
        cl = broker.clients.get("v4sub")
        cases = [
            (Subscription(filter="t/f", qos=0), 0, False, "bytes"),
            (Subscription(filter="t/f", qos=0, retain_as_published=True),
             0, True, "tuple"),
            (Subscription(filter="t/f", qos=1), 1, False, "tuple"),
            (Subscription(filter="t/f", qos=2, retain_as_published=True),
             2, True, "tuple"),
        ]
        for sub, qos, retain, expect in cases:
            wire = await _deliver(
                broker, cl, sub,
                _pub(qos=qos, retain=retain, props=_rich_props()), expect)
            assert b"application/json" not in wire  # v5 props stripped
        await c.disconnect()


@pytest.mark.parametrize("native", [True, False])
async def test_differential_matrix_v5(native):
    """v5 feature matrix; ``native`` False pins the pure-Python head
    builder to the same bytes as the C one."""
    async with running_broker(native_encode=native) as broker:
        c = await connect(broker, "v5sub", version=5)
        cl = broker.clients.get("v5sub")
        sid = Subscription(filter="t/f", qos=0, identifier=7)
        merged = Subscription(filter="t/f", qos=0,
                              identifiers={"a/#": 3, "b/#": 9, "c/#": 3})
        cases = [
            (Subscription(filter="t/f", qos=0), 0, False, "bytes"),
            (sid, 0, False, "tuple"),
            (merged, 0, False, "tuple"),
            (Subscription(filter="t/f", qos=0, retain_as_published=True),
             0, True, "tuple"),
            (Subscription(filter="t/f", qos=1), 1, False, "tuple"),
            (Subscription(filter="t/f", qos=1, identifier=3), 1, False,
             "tuple"),
            (Subscription(filter="t/f", qos=2, identifier=1,
                          retain_as_published=True), 2, True, "tuple"),
        ]
        for sub, qos, retain, expect in cases:
            await _deliver(broker, cl, sub,
                           _pub(qos=qos, retain=retain,
                                props=_rich_props()), expect)
        # splice with an empty shared property block, and with an
        # empty payload (degenerate shared segments)
        await _deliver(broker, cl, sid, _pub(qos=1), "tuple")
        await _deliver(broker, cl, sid, _pub(payload=b"",
                                             props=_rich_props()), "tuple")
        await c.disconnect()


async def test_differential_topic_alias_progression():
    """Outbound alias lifecycle through the template path: first use
    carries topic + alias, repeats carry the alias with an empty
    topic — each frame byte-equal to the slow path at the same point
    in the progression."""
    async with running_broker() as broker:
        c = await connect(broker, "al", version=5)
        cl = broker.clients.get("al")
        cl.properties.topic_alias_maximum = 8  # as advertised in CONNECT
        sub = Subscription(filter="t/f", qos=0, identifier=4)
        topic = "alias/long/topic/name"
        b0 = broker.overload.template_builds
        packet = _pub(topic=topic, props=_rich_props())
        first = await _deliver(broker, cl, sub, packet, "tuple")
        second = await _deliver(broker, cl, sub, packet, "tuple")
        assert topic.encode() in first
        assert topic.encode() not in second     # alias replaced the topic
        assert len(second) < len(first)
        # one template build served both deliveries (per-packet cache)
        assert broker.overload.template_builds - b0 == 1
        # QoS1 to an established alias still patches correctly
        await _deliver(broker, cl, sub, _pub(topic=topic, qos=1), "tuple")
        await c.disconnect()


async def test_differential_max_packet_size():
    """A client maximum-packet-size no longer disqualifies the
    template path — only a frame that could EXCEED it falls back to
    the slow path (where encode_under may still shed properties)."""
    async with running_broker() as broker:
        c = await connect(broker, "mps", version=5)
        cl = broker.clients.get("mps")
        sub = Subscription(filter="t/f", qos=0, identifier=2)
        cl.properties.maximum_packet_size = 4096
        await _deliver(broker, cl, sub, _pub(props=_rich_props()), "tuple")
        cl.properties.maximum_packet_size = 30   # frame cannot fit
        await _deliver(broker, cl, sub, _pub(props=_rich_props()), "packet")
        await _deliver(broker, cl, sub, _pub(qos=1, props=_rich_props()),
                       "packet")
        await c.disconnect()


async def test_hook_and_send_seams_force_slow_path():
    """Encode/sent hook overrides and an instance-patched send method
    must observe real mutable Packets: both disqualify the template."""
    class EncodeTap(Hook):
        id = "encode-tap"

        def on_packet_encode(self, packet, client):
            return packet

    async with running_broker() as broker:
        broker.add_hook(EncodeTap())
        c = await connect(broker, "hooked", version=5)
        cl = broker.clients.get("hooked")
        sub = Subscription(filter="t/f", qos=0, identifier=9)
        await _deliver(broker, cl, sub, _pub(props=_rich_props()), "packet")
        await _deliver(broker, cl, sub, _pub(qos=1), "packet")
        await c.disconnect()
    async with running_broker() as broker:
        c = await connect(broker, "seamed", version=5)
        cl = broker.clients.get("seamed")
        # the embedder/test seam: an instance-level send wrapper
        cl.send = lambda p, **kw: type(cl).send(cl, p, **kw)
        sub = Subscription(filter="t/f", qos=0, identifier=9)
        await _deliver(broker, cl, sub, _pub(props=_rich_props()), "packet")
        await c.disconnect()


async def test_template_cache_shared_across_subscribers():
    """One publish, three template subscribers: one build, three
    sends, shared bytes ≥ the frame tail for each."""
    async with running_broker() as broker:
        cs = [await connect(broker, f"s{i}", version=5) for i in range(3)]
        cls = [broker.clients.get(f"s{i}") for i in range(3)]
        sub = Subscription(filter="t/f", qos=0, identifier=5)
        packet = _pub(props=_rich_props())
        ov = broker.overload
        b0, s0, sh0, cp0 = (ov.template_builds, ov.template_sends,
                            ov.shared_bytes, ov.copied_bytes)
        for cl in cls:
            await _deliver(broker, cl, sub, packet, "tuple")
        assert ov.template_builds - b0 == 1
        assert ov.template_sends - s0 == 3
        shared, copied = ov.shared_bytes - sh0, ov.copied_bytes - cp0
        assert shared > copied > 0  # payload+props shared, heads copied
        for c in cs:
            await c.disconnect()


# -- satellite 3: end-to-end through real sockets ----------------------


async def test_template_path_e2e_ledger_exactness():
    """Retain-as-published delivery over a real socket: the frame
    parses in the client, and the bytes the writer put on the wire
    equal the bytes charged at enqueue (shared + copied ledger)."""
    async with running_broker() as broker:
        s = await connect(broker, "rapsub", version=5)
        await s.subscribe(("rap/t", 0), retain_as_published=True)
        p = await connect(broker, "pub", version=5)
        await asyncio.sleep(0.05)
        ov, info = broker.overload, broker.info
        b0 = info.bytes_sent
        z0 = ov.shared_bytes + ov.copied_bytes
        t0, sl0 = ov.template_sends, ov.slow_encodes
        await p.publish("rap/t", b"r" * 256, retain=True)
        msg = await s.next_message()
        assert (msg.topic, msg.payload, msg.retain) == \
            ("rap/t", b"r" * 256, True)
        await poll(lambda: ov.template_sends - t0 == 1, what="template send")
        await asyncio.sleep(0.1)  # writer flush settles bytes_sent
        assert ov.slow_encodes == sl0
        wire_bytes = (ov.shared_bytes + ov.copied_bytes) - z0
        assert info.bytes_sent - b0 == wire_bytes > 0
        await s.disconnect()
        await p.disconnect()


async def test_hook_override_e2e_takes_slow_path():
    """With an on_packet_sent observer installed the whole fan-out
    reverts to per-subscriber encodes — and still delivers."""
    class SentTap(Hook):
        id = "sent-tap"

        def __init__(self):
            self.publishes = 0

        def on_packet_sent(self, client, packet, nbytes):
            if packet.type == PT.PUBLISH:
                self.publishes += 1

    tap = SentTap()
    async with running_broker() as broker:
        broker.add_hook(tap)
        s = await connect(broker, "sub", version=5)
        await s.subscribe("h/#")
        p = await connect(broker, "pub")
        await p.publish("h/t", b"one")
        await p.publish("h/t", b"two", qos=1)
        assert (await s.next_message()).payload == b"one"
        assert (await s.next_message()).payload == b"two"
        await poll(lambda: tap.publishes >= 2, what="sent hook saw both")
        assert broker.overload.slow_encodes >= 2
        assert broker.overload.template_sends == 0
        await s.disconnect()
        await p.disconnect()


async def test_fanout_flush_coalescing_and_writev():
    """1->N fan-out serves each writer once per loop iteration: one
    park, one burst through one writelines (not one per delivery), and
    the pass writes an idle writer's burst itself (direct)."""
    async with running_broker() as broker:
        subs = [await connect(broker, f"w{i}") for i in range(3)]
        for s in subs:
            await s.subscribe("f/t")
        p = await connect(broker, "pub")
        await asyncio.sleep(0.05)
        sched, ov = broker.flush_sched, broker.overload
        assert sched is not None
        f0, d0, w0 = sched.flushes, sched.deferred, ov.writev_batches
        x0, woken0 = sched.direct, dict(sched.woken)
        await p.publish("f/t", b"burst")
        for s in subs:
            assert (await s.next_message()).payload == b"burst"
        assert sched.deferred - d0 >= 3     # one parked wake per writer
        assert sched.flushes - f0 >= 1
        assert sched.direct - x0 >= 3       # ...each written by the pass
        assert sched.woken == woken0        # no writer task woke for it
        await poll(lambda: ov.writev_batches - w0 >= 3, what="writev flush")
        for c in subs + [p]:
            await c.disconnect()


async def test_subscriber_hit_k_times_in_one_step_gets_one_writev():
    """Eight publishes arriving in one segment are delivered in one
    step of the pipeline's consumer: the subscriber is parked once
    (seven wakes absorbed) and its eight deliveries leave in ONE
    writev, written where the consumer ran dry."""
    class _Trie:
        """Answers at enqueue, as the batcher's topic cache does."""
        def __init__(self, index): self.index = index
        def enqueue(self, topic):
            fut = asyncio.get_running_loop().create_future()
            fut.set_result(self.index.subscribers(topic))
            return fut

    async with running_broker() as broker:
        broker.attach_matcher(_Trie(broker.topics))
        s = await connect(broker, "k-sub")
        await s.subscribe("k/#")
        p = await connect(broker, "k-pub")
        await asyncio.sleep(0.05)
        sched, ov = broker.flush_sched, broker.overload
        w0, b0, x0, c0 = (ov.writev_batches, ov.writev_buffers,
                          sched.direct, sched.coalesced)
        frames = b"".join(
            Packet(fixed=FixedHeader(type=PT.PUBLISH), protocol_version=4,
                   topic=f"k/{i}", payload=b"%d" % i).encode()
            for i in range(8))
        p.writer.write(frames)              # one segment, one read
        got = [await s.next_message(timeout=5) for _ in range(8)]
        assert [m.payload for m in got] == [b"%d" % i for i in range(8)]
        assert ov.writev_batches - w0 == 1
        assert ov.writev_buffers - b0 == 8
        assert sched.direct - x0 == 1
        assert sched.coalesced - c0 == 7
        await s.disconnect()
        await p.disconnect()


# -- satellite 4: fast/template drops feed the slow path's ledgers -----


async def _drop_parity(broker, sub_client_id: str, reason: str):
    cl = broker.clients.get(sub_client_id)
    await poll(lambda: cl.dropped_msgs > 0, what="drops recorded")
    drops = cl.drops_by_reason.get(reason, 0)
    assert drops > 0, f"expected {reason} drops, saw {cl.drops_by_reason}"
    assert broker.tracer.stage_errors.get(("drain", reason), 0) == drops
    return drops


async def test_fast_path_budget_drops_feed_ledgers():
    """bytes fast path: oldest-first QoS0 shedding lands in the same
    three ledgers the slow path uses."""
    async with running_broker(client_byte_budget=2048) as broker:
        s = await connect(broker, "slow4", version=4)
        await s.subscribe("d/t")
        stall_writer("slow4")
        p = await connect(broker, "pub")
        for _ in range(24):
            await p.publish("d/t", b"z" * 400)
        drops = await _drop_parity(broker, "slow4", "byte_budget")
        assert broker.overload.budget_drops >= drops
        await p.disconnect()


async def test_template_path_budget_drops_feed_ledgers():
    """tuple template path (retain-as-published): identical refusal
    accounting, and the path taken really was the template."""
    async with running_broker(client_byte_budget=2048) as broker:
        s = await connect(broker, "slow5", version=5)
        await s.subscribe(("d/t", 0), retain_as_published=True)
        stall_writer("slow5")
        p = await connect(broker, "pub")
        for _ in range(24):
            await p.publish("d/t", b"z" * 400, retain=True)
        drops = await _drop_parity(broker, "slow5", "byte_budget")
        assert broker.overload.budget_drops >= drops
        assert broker.overload.template_sends > 0
        await p.disconnect()


async def test_template_path_queue_full_drops_feed_ledgers():
    async with running_broker(maximum_client_writes_pending=4) as broker:
        s = await connect(broker, "qf", version=5)
        await s.subscribe(("d/t", 0), retain_as_published=True)
        stall_writer("qf")
        p = await connect(broker, "pub")
        for _ in range(16):
            await p.publish("d/t", b"z" * 64, retain=True)
        await _drop_parity(broker, "qf", "queue_full")
        await p.disconnect()


async def test_template_qos1_refusal_rolls_back_like_slow_path():
    """A refused QoS1 template delivery follows the ADR-012 rollback:
    qos_drops counted, inflight entry gone, no quota leak."""
    async with running_broker(client_byte_budget=2048) as broker:
        s = await connect(broker, "q1", version=5)
        await s.subscribe(("d/t", 1), retain_as_published=True)
        stall_writer("q1")
        p = await connect(broker, "pub", version=5)
        for _ in range(8):
            await p.publish("d/t", b"z" * 700, qos=1, retain=True)
        cl = broker.clients.get("q1")
        await poll(lambda: broker.overload.qos_drops > 0, what="qos rollback")
        assert cl.drops_by_reason.get("byte_budget", 0) > 0
        # rollback left no orphaned inflight entries behind the ledger
        assert broker.info.inflight == len(cl.inflight.all())
        assert broker.tracer.stage_errors.get(("drain", "byte_budget"), 0) \
            == cl.drops_by_reason["byte_budget"]
        await p.disconnect()


# -- satellite 2: byte-accounting exactness ----------------------------


def test_estimate_wire_counts_v5_properties():
    """The residual Packet-path estimate must cover the variable v5
    properties — an adversarial publisher cannot hide a kilobyte of
    user properties under a flat allowance — while staying within the
    32-byte header slack of the true encoding."""
    pr = Properties(content_type="application/json",
                    response_topic="reply/to/me",
                    correlation_data=b"c" * 32,
                    user_properties=[("k1", "v" * 500), ("k2", "w" * 500)])
    p = Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=1),
               protocol_version=5, topic="a/b", payload=b"p" * 100,
               packet_id=5, properties=pr)
    est, actual = _estimate_wire(p), len(p.encode())
    assert actual <= est <= actual + 32
    assert est - (32 + len(p.topic) + len(p.payload)) > 1000
    # v4 form of the same packet: flat allowance still covers it
    p4 = p.copy()
    p4.protocol_version = 4
    p4.properties = Properties()
    assert len(p4.encode()) <= _estimate_wire(p4)


def test_estimate_wire_non_publish_flat():
    ack = Packet(fixed=FixedHeader(type=PT.PUBACK), packet_id=3)
    assert _estimate_wire(ack) == 32


# -- tentpole: native head builder vs Python fallback ------------------


def test_native_head_differential_fuzz():
    """5000 seeded-random head shapes through the C builder and the
    Python fallback: flags, topic segments up to 300B, every packet-id
    form, property lengths crossing each varint width boundary (incl.
    -1 = v3 no-props frames), payload tails up to 300KB. Byte-identical
    or the zero-copy frames are wrong at the socket."""
    import random

    from maxmq_tpu.protocol.wire import (_encode_head_py, encode_head,
                                         native_head_encoder)

    enc = native_head_encoder(build=True)
    if enc is None:
        pytest.skip("native extension unavailable")
    rng = random.Random(0x019)
    boundary = (0, 1, 127, 128, 16383, 16384, 2097151, 2097152)
    for _ in range(5000):
        flags = 0x30 | rng.randrange(16)
        tlen = rng.choice((0, 1, 7, 64, 300))
        topic_seg = tlen.to_bytes(2, "big") + bytes(
            rng.randrange(256) for _ in range(tlen))
        pid = rng.choice((0, 1, 255, 256, 65535, rng.randrange(1, 65536)))
        props_len = rng.choice((-1,) + boundary + (rng.randrange(0, 1 << 21),))
        tail = rng.choice(boundary[:-2] + (300000,))
        got = enc(flags, topic_seg, pid, props_len, tail)
        want = _encode_head_py(flags, topic_seg, pid, props_len, tail)
        assert got == want, (flags, tlen, pid, props_len, tail)
    # the dispatching wrapper agrees with both
    assert encode_head(0x33, b"\x00\x01a", 7, 42, 9) == \
        _encode_head_py(0x33, b"\x00\x01a", 7, 42, 9)


async def test_retained_at_subscribe_carries_subscription_id():
    """[MQTT-3.3.4-3]: the retained message delivered when a
    subscription is established carries that subscription's identifier
    like any forwarded publish (regression: _send_retained used to
    deliver the stored properties untouched)."""
    async with running_broker() as broker:
        pub = await connect(broker, "rpub", version=5)
        await pub.publish("ret/a", b"stored", retain=True)
        await pub.disconnect()

        sub = await connect(broker, "rsub", version=5)
        pid = sub._alloc_id()
        pkt = Packet(fixed=FixedHeader(type=PT.SUBSCRIBE),
                     protocol_version=5, packet_id=pid,
                     filters=[Subscription(filter="ret/+", qos=0)],
                     properties=Properties(subscription_ids=[42]))
        fut = sub._await_ack(PT.SUBACK, pid)
        sub.writer.write(pkt.encode())
        await sub.writer.drain()
        await asyncio.wait_for(fut, 5)
        msg = await asyncio.wait_for(sub.next_message(), 5)
        assert msg.retain and msg.payload == b"stored"
        assert msg.properties.subscription_ids == [42]
        await sub.disconnect()
