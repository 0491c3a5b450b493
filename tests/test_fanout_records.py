"""ADR 019 carried to the session state and the journal: one shaped
packet and one spliced record for a QoS >= 1 delivery.

What every receiver of a publish shares is built once a publish; this
file holds what that must not change:

* the differential matrix: for v3.1.1 / v5 x QoS 1 / 2 x
  retain-as-published x property shapes x {sent, held on send quota}
  the string put into the ``inflight`` bucket is
  ``MessageRecord.from_packet(entry, client.id)`` with ``held``,
  ``.to_json()``, byte for byte; the inflight entry equals, field by
  field, what the copying path of before left there (kept below as
  ``_copied_shape``); the bytes queued are that packet's encoding;
* aliasing: two receivers' entries share no mutable object with each
  other or with the source publish, and a resend on session resume sets
  DUP on the wire and on nothing the session keeps;
* restart: a broker booted on the store after a 100-receiver QoS 1
  broadcast that nobody acknowledged restores the same 100 entries;
* the ledger: ``records_spliced + records_built`` is the number of
  records the storage hook put.
"""

import copy
import dataclasses
import itertools
import json

import pytest

from test_broker_system import connect
from test_wire_templates import _pub, _rich_props, poll

from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities, TCPListener
from maxmq_tpu.hooks import AllowHook
from maxmq_tpu.hooks.journal import WriteBehindStore
from maxmq_tpu.hooks.storage import (MemoryStore, MessageRecord, SQLiteStore,
                                     StorageHook, _spliced_record)
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.protocol.codec import FixedHeader
from maxmq_tpu.protocol.codec import PacketType as PT
from maxmq_tpu.protocol.packets import Packet, Subscription
from maxmq_tpu.protocol.properties import Properties


class CountingStore(MemoryStore):
    """A MemoryStore that counts what the hook puts into ``inflight``."""

    def __init__(self) -> None:
        super().__init__()
        self.inflight_puts = 0

    def put(self, bucket, key, value):
        if bucket == "inflight":
            self.inflight_puts += 1
        super().put(bucket, key, value)


async def _storing_broker(store, **caps) -> Broker:
    caps.setdefault("sys_topic_interval", 0)
    b = Broker(BrokerOptions(capabilities=Capabilities(**caps)))
    b.add_hook(AllowHook())
    b.add_hook(StorageHook(store))
    listener = b.add_listener(TCPListener("t1", "127.0.0.1:0"))
    await b.serve()
    b.test_port = listener._server.sockets[0].getsockname()[1]
    return b


def _copied_shape(broker, client, sub, packet) -> Packet:
    """The delivery as the copying path shaped it before the publish's
    shared part was built once: ``Packet.copy()`` of the source, then
    the receiver's fields. The reference of the matrix."""
    out = packet.copy()
    out.protocol_version = client.properties.protocol_version
    out.fixed.qos = min(packet.fixed.qos, sub.qos,
                        broker.capabilities.maximum_qos)
    out.fixed.dup = False
    if not sub.retain_as_published:
        out.fixed.retain = False
    if client.properties.protocol_version < 5:
        out.properties = Properties()
        return out
    out.properties.subscription_ids = sorted(
        set(sub.identifiers.values())
        or ({sub.identifier} if sub.identifier else set()))
    out.properties.topic_alias = None
    if client.aliases is not None and client.properties.topic_alias_maximum:
        alias, first = client.aliases.assign_outbound(out.topic)
        if alias:
            out.properties.topic_alias = alias
            if not first:
                out.topic = ""
    return out


def _assert_same_fields(entry: Packet, ref: Packet) -> None:
    for f in dataclasses.fields(Packet):
        assert getattr(entry, f.name) == getattr(ref, f.name), f.name


async def _deliver_recorded(broker, cl, sub, packet, held: bool):
    """One QoS >= 1 delivery through ``_publish_to_client`` with the
    outbound queue intercepted, checked against ``_copied_shape``:
    (a) the record, (b) the inflight entry, (c) the bytes queued.
    Returns (entry, whether its record was spliced)."""
    store = broker._storage_hook.store
    over = broker.overload
    aliases = copy.deepcopy(cl.aliases)
    ref = _copied_shape(broker, cl, sub, packet)
    cl.aliases = aliases        # the reference consumed no alias
    before = {p.packet_id for p in cl.inflight.all()}
    spliced0, built0 = over.records_spliced, over.records_built
    queued: list = []
    cl.outbound.put_nowait = lambda item, size=0: queued.append((item, size))
    try:
        broker._publish_to_client(cl, sub, packet, shared=False)
    finally:
        del cl.outbound.put_nowait
    new = [p for p in cl.inflight.all() if p.packet_id not in before]
    assert len(new) == 1, "a QoS>0 delivery registers one inflight entry"
    entry = new[0]
    ref.packet_id, ref.created = entry.packet_id, entry.created
    # (b) the entry, field by field
    _assert_same_fields(entry, ref)
    assert entry.fixed.dup is False
    assert "_src" not in entry.__dict__     # named for the notify only
    # (a) the record, byte for byte
    want = MessageRecord.from_packet(ref, cl.id)
    want.held = held
    raw = store.get("inflight", f"{cl.id}|{entry.packet_id}")
    assert raw == want.to_json()
    assert MessageRecord.from_json(raw).to_packet() == want.to_packet()
    assert (over.records_spliced - spliced0
            + over.records_built - built0) == 1
    # (c) the wire
    if held:
        assert not queued and entry.packet_id in cl.held_pids
    else:
        assert len(queued) == 1
        item, size = queued[0]
        assert type(item) is tuple, "took the slow path"
        assert b"".join(item) == ref.encode() and size == len(ref.encode())
    return entry, over.records_spliced - spliced0 == 1


# version, shape of the publish's properties / the subscription
_SHAPES = ([(4, s) for s in ("plain", "rich")]
           + [(5, s) for s in ("plain", "rich", "sid", "merged", "alias")])
MATRIX = [(v, shape, qos, rap, held)
          for (v, shape), qos, rap, held in itertools.product(
              _SHAPES, (1, 2), (False, True), (False, True))]


@pytest.mark.parametrize(
    "version,shape,qos,rap,held", MATRIX,
    ids=[f"v{v}-{s}-q{q}-{'rap' if r else 'norap'}-{'held' if h else 'sent'}"
         for v, s, q, r, h in MATRIX])
async def test_record_entry_and_wire_match_the_copying_path(
        version, shape, qos, rap, held):
    broker = await _storing_broker(CountingStore())
    try:
        c = await connect(broker, "rx", version=version, clean_start=False)
        cl = broker.clients.get("rx")
        sub = Subscription(filter="t/f", qos=qos, retain_as_published=rap)
        if shape == "sid":
            sub.identifier = 7
        elif shape == "merged":
            sub.identifiers = {"a/#": 3, "b/#": 9, "c/#": 3}
        elif shape == "alias":
            cl.properties.topic_alias_maximum = 8   # as CONNECT advertises
        if held:
            cl.inflight.maximum_send, cl.inflight.send_quota = 1, 0
        props = None if shape == "plain" else _rich_props()
        packet = _pub(topic="plant/7/line/3/state", payload=b"\x00\xffpay",
                      qos=2, retain=True, props=props)
        packet.origin = 'pub-"1"\\é'       # what JSON has to escape
        entry, spliced = await _deliver_recorded(broker, cl, sub, packet,
                                                 held)
        # per-receiver identifiers make the record the receiver's own
        assert spliced == (shape not in ("sid", "merged") or version < 5)
        if shape == "alias":
            # the repeat carries the alias in the topic's place, so its
            # record has its own (empty) topic and is built whole
            again, spliced = await _deliver_recorded(broker, cl, sub,
                                                     packet, held)
            assert again.topic == "" and not spliced
        store = broker._storage_hook.store
        over = broker.overload
        assert over.records_spliced + over.records_built \
            == store.inflight_puts
        await c.close()
    finally:
        await broker.close()


@pytest.mark.parametrize("client_id,retain,created", [
    ("plain", False, 1759446000.123456),
    ('q"uo\\te\n', True, 0.1),
    ("ünï-码", 1, 1e22),           # a retain flag that is no bool
    ("", False, 5e-324),
])
def test_spliced_record_is_to_json_byte_for_byte(client_id, retain, created):
    """The splice against ``to_json`` with no broker around it: client
    ids JSON has to escape, floats at both ends of ``repr``, and a
    retain flag that is no bool, which is left to ``to_json``."""
    src = _pub(topic="a/ b\t", payload=bytes(range(256)), qos=1,
               retain=True, props=_rich_props())
    src.origin = "o\x7f"
    for version, held in itertools.product((4, 5), (False, True)):
        out = src.delivery(version, 1, retain)
        out.packet_id, out.created = 65535, created
        want = MessageRecord.from_packet(out, client_id)
        want.held = held
        got = _spliced_record(client_id, out, src, held)
        if type(retain) is bool:
            assert got == want.to_json()
            assert json.loads(got) == json.loads(want.to_json())
        else:
            assert got is None


def test_publish_with_identifiers_of_its_own_is_not_shared_with_v5():
    """A publish that itself carries subscription identifiers (only an
    embedder can make one): a v5 receiver's copy replaces them, so its
    record is built whole; a v3.1.1 receiver's has no properties and
    splices as ever."""
    src = _pub(qos=1, props=Properties(subscription_ids=[5]))
    for version in (4, 5):
        out = src.delivery(version, 1, False)
        out.properties.subscription_ids = []
        out.packet_id, out.created = 3, 12.5
        got = _spliced_record("c", out, src, False)
        if version >= 5:
            assert got is None
        else:
            assert got == MessageRecord.from_packet(out, "c").to_json()


async def test_receivers_of_one_publish_share_no_mutable_object():
    broker = await _storing_broker(CountingStore())
    try:
        conns = [await connect(broker, f"rx{i}", version=5,
                               clean_start=False) for i in range(2)]
        sub = Subscription(filter="t/f", qos=1, identifier=4)
        packet = _pub(qos=1, props=_rich_props())
        entries = []
        for i in range(2):
            cl = broker.clients.get(f"rx{i}")
            cl.outbound.put_nowait = lambda item, size=0: None
            broker._publish_to_client(cl, sub, packet, shared=False)
            entries.append(cl.inflight.all()[0])

        def mutables(p: Packet) -> list:
            return [p, p.fixed, p.properties, p.properties.subscription_ids,
                    p.properties.user_properties, p.reason_codes, p.filters]

        ids = [id(o) for p in (*entries, packet) for o in mutables(p)]
        assert len(ids) == len(set(ids))
        # and neither reaches the publish: what named it is gone
        assert all("_src" not in e.__dict__ for e in entries)
        for c in conns:
            await c.close()
    finally:
        await broker.close()


def _no_ack(client: MQTTClient) -> list:
    """Make ``client`` take PUBLISHes without acknowledging them;
    returns the list they collect in."""
    seen: list = []

    async def grab(packet: Packet) -> None:
        seen.append(packet)
    client._handle_publish = grab
    return seen


async def test_resume_resend_sets_dup_on_the_wire_alone():
    store = CountingStore()
    broker = await _storing_broker(store)
    try:
        sub = await connect(broker, "sleeper", clean_start=False)
        await sub.subscribe(("night/+", 1))
        seen = _no_ack(sub)
        pub = await connect(broker, "pub")
        await pub.publish("night/1", b"first", qos=1)
        await poll(lambda: seen, what="first transmission")
        assert seen[0].fixed.dup is False
        pid = seen[0].packet_id
        key = f"sleeper|{pid}"
        record = store.get("inflight", key)
        assert record is not None and store.inflight_puts == 1
        await sub.close()
        await poll(lambda: broker.clients.get("sleeper").closed)

        back = MQTTClient(client_id="sleeper", clean_start=False)
        again = _no_ack(back)
        await back.connect("127.0.0.1", broker.test_port)
        assert back.connack.session_present is True
        await poll(lambda: again, what="the resend")
        assert again[0].fixed.dup is True and again[0].packet_id == pid
        entry = broker.clients.get("sleeper").inflight.get(pid)
        assert entry.fixed.dup is False
        assert store.get("inflight", key) == record
        assert store.inflight_puts == 1     # the rewrite was skipped
        assert broker._storage_hook.rewrites_skipped == 1
        await back.close()
        await pub.disconnect()
    finally:
        await broker.close()


async def test_restart_restores_an_unacked_broadcast(tmp_path):
    """100 receivers, one QoS 1 broadcast, no PUBACK: a second broker on
    the same SQLite file holds the 100 entries the first one did."""
    path = str(tmp_path / "fanout.db")

    def store():
        return WriteBehindStore(SQLiteStore(path), policy="batched")

    b1 = await _storing_broker(store())
    n = 100
    conns = []
    for i in range(n):
        c = MQTTClient(client_id=f"dev-{i:03d}", clean_start=False)
        await c.connect("127.0.0.1", b1.test_port)
        await c.subscribe(("fleet/cmd", 1))
        _no_ack(c)
        conns.append(c)
    pub = await connect(b1, "operator")
    await pub.publish("fleet/cmd", b"reboot", qos=1)
    await poll(lambda: b1.info.inflight == n, what="100 inflight entries")
    over = b1.overload
    assert (over.records_spliced, over.records_built) == (n, 0)
    expected = {}
    for i in range(n):
        cid = f"dev-{i:03d}"
        cl = b1.clients.get(cid)
        (entry,) = cl.inflight.all()
        sub = cl.subscriptions["fleet/cmd"]
        src = Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=1),
                     topic="fleet/cmd", payload=b"reboot",
                     origin="operator")
        ref = _copied_shape(b1, cl, sub, src)
        ref.packet_id, ref.created = entry.packet_id, entry.created
        ref.fixed.remaining = entry.fixed.remaining
        _assert_same_fields(entry, ref)
        expected[cid] = MessageRecord.from_json(
            MessageRecord.from_packet(ref, cid).to_json()).to_packet()
    for c in conns:
        await c.close()
    await pub.disconnect()
    await b1.close()

    b2 = await _storing_broker(store())
    try:
        assert b2.info.inflight == n
        for cid, want in expected.items():
            (got,) = b2.clients.get(cid).inflight.all()
            assert got == want
    finally:
        await b2.close()
