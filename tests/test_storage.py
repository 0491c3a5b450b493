"""Storage hook + stores (hooks/storage.py): record round-trips, both
backends, the write-through event surface, and full broker restore —
plus the ADR-014 crash-consistent pipeline: write-behind journal
(group commit, coalescing, durability barriers), storage degradation
breaker, per-record quarantine, SQLite integrity move-aside, and the
persisted boot epoch.

Parity surface: the reference's hooks/storage types + Stored* plumbing
(vendor/.../v2/hooks/storage/storage.go:29-193, server.go:1297-1434);
it vendors no backend — this repo's Memory/SQLite stores exceed it."""

import asyncio
import json
import threading
import time

import pytest
from test_broker_system import connect, running_broker

from maxmq_tpu import faults
from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities, TCPListener
from maxmq_tpu.broker.inflight import Inflight
from maxmq_tpu.hooks import AllowHook
from maxmq_tpu.hooks.journal import (BREAKER_CLOSED, BREAKER_OPEN,
                                     WriteBehindStore)
from maxmq_tpu.hooks.storage import (ClientRecord, MemoryStore,
                                     MessageRecord, SQLiteStore,
                                     StorageHook, SubscriptionRecord)
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.protocol.codec import FixedHeader, PacketType as PT
from maxmq_tpu.protocol.packets import Packet, Properties


class GatedStore(MemoryStore):
    """MemoryStore whose apply_batch blocks on an event and/or raises on
    command — deterministic control over the journal writer thread."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()
        self.gate.set()
        self.fail = False
        self.batches = 0

    def apply_batch(self, ops):
        self.gate.wait(timeout=10.0)
        if self.fail:
            raise OSError("injected backend failure")
        self.batches += 1
        super().apply_batch(ops)


def test_record_json_round_trips():
    c = ClientRecord(client_id="c1", listener="tcp", username=b"u\xff",
                     clean=True, protocol_version=5, session_expiry=30,
                     session_expiry_set=True, disconnected_at=12.5)
    c2 = ClientRecord.from_json(c.to_json())
    assert (c2.client_id, c2.protocol_version, c2.session_expiry,
            c2.session_expiry_set) == ("c1", 5, 30, True)

    s = SubscriptionRecord(client_id="c1", filter="a/+", qos=2,
                           no_local=True, retain_as_published=True,
                           retain_handling=2, identifier=7)
    assert SubscriptionRecord.from_json(s.to_json()) == s

    m = MessageRecord(client_id="c1", topic="t/x", payload=b"\x00\xffp",
                      qos=1, retain=True, packet_id=9, created=1.0)
    m2 = MessageRecord.from_json(m.to_json())
    assert m2.payload == b"\x00\xffp" and m2.packet_id == 9


def test_message_record_packet_round_trip_v5_properties():
    p = Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=1, retain=True),
               topic="t/v5", payload=b"body", packet_id=3,
               origin="orig", created=2.0,
               properties=Properties(
                   payload_format=1, message_expiry=60,
                   content_type="text/plain", response_topic="r/t",
                   correlation_data=b"\x01\x02",
                   user_properties=[("k", "v")]))
    rec = MessageRecord.from_packet(p, client_id="c9")
    back = MessageRecord.from_json(rec.to_json()).to_packet()
    assert back.topic == "t/v5" and back.payload == b"body"
    assert back.fixed.qos == 1 and back.fixed.retain
    assert back.properties.content_type == "text/plain"
    assert back.properties.correlation_data == b"\x01\x02"
    assert back.properties.user_properties == [("k", "v")]
    assert back.properties.message_expiry == 60


def test_sqlite_store_operations_and_persistence(tmp_path):
    path = str(tmp_path / "s.db")
    st = SQLiteStore(path)
    st.put("b1", "k1", "v1")
    st.put("b1", "k2", "v2")
    st.put("b2", "k1", "other")
    assert st.get("b1", "k1") == "v1"
    assert st.get("b1", "missing") is None
    assert st.all("b1") == {"k1": "v1", "k2": "v2"}
    st.delete("b1", "k1")
    assert st.get("b1", "k1") is None
    st.put("b1", "pre:a", "1")
    st.put("b1", "pre:b", "2")
    st.delete_prefix("b1", "pre:")
    assert st.all("b1") == {"k2": "v2"}
    st.close()
    st2 = SQLiteStore(path)            # survives reopen
    assert st2.get("b2", "k1") == "other"
    st2.close()


def test_memory_store_prefix_delete():
    st = MemoryStore()
    st.put("b", "x:1", "a")
    st.put("b", "x:2", "b")
    st.put("b", "y:1", "c")
    st.delete_prefix("b", "x:")
    assert st.all("b") == {"y:1": "c"}


async def test_write_through_events_and_expiry_cleanup():
    """The hook's event surface against MemoryStore: session, subs,
    retained, inflight write-through; expiry deletes everything."""
    store = MemoryStore()
    async with running_broker() as broker:
        broker.add_hook(StorageHook(store))
        c = await connect(broker, "st-c1", version=4, clean_start=False)
        await c.subscribe(("st/+", 1))
        assert store.all("clients")           # session persisted
        assert any("st/+" in v for v in store.all("subscriptions").values())
        p = await connect(broker, "st-pub")
        await p.publish("st/keep", b"r", qos=0, retain=True)
        await asyncio.sleep(0.05)
        assert any("st/keep" in v for v in store.all("retained").values())
        # clear the retained message -> record removed
        await p.publish("st/keep", b"", qos=0, retain=True)
        await asyncio.sleep(0.05)
        assert not any("st/keep" in v
                       for v in store.all("retained").values())
        await c.unsubscribe("st/+")
        await asyncio.sleep(0.05)
        assert not any('"st/+"' in v
                       for v in store.all("subscriptions").values())
        await c.disconnect()
        await p.disconnect()


async def test_full_restore_across_broker_restart(tmp_path):
    """Offline QoS1 redelivery across a full broker restart (the
    reference's readStore path, server.go:1297-1434): persistent
    session + inflight + retained all restore from SQLite."""
    path = str(tmp_path / "restore.db")

    async def start(port_holder):
        b = Broker(BrokerOptions(capabilities=Capabilities(
            sys_topic_interval=0)))
        b.add_hook(AllowHook())
        b.add_hook(StorageHook(SQLiteStore(path)))
        lst = b.add_listener(TCPListener("t", "127.0.0.1:0"))
        await b.serve()
        port_holder.append(lst._server.sockets[0].getsockname()[1])
        return b

    ports: list[int] = []
    b1 = await start(ports)
    sub = MQTTClient(client_id="rs-sub", clean_start=False)
    await sub.connect("127.0.0.1", ports[0])
    await sub.subscribe(("rs/x", 1))
    await sub.disconnect()
    pub = MQTTClient(client_id="rs-pub")
    await pub.connect("127.0.0.1", ports[0])
    await pub.publish("rs/x", b"queued", qos=1)
    await pub.publish("rs/ret", b"kept", qos=0, retain=True)
    await asyncio.sleep(0.1)
    await pub.disconnect()
    await b1.close()

    b2 = await start(ports)            # fresh broker, same store
    try:
        sub2 = MQTTClient(client_id="rs-sub", clean_start=False)
        await sub2.connect("127.0.0.1", ports[1])
        assert sub2.connack.session_present is True
        m = await sub2.next_message(timeout=10)
        assert m.payload == b"queued"  # offline inflight redelivered
        fresh = MQTTClient(client_id="rs-fresh")
        await fresh.connect("127.0.0.1", ports[1])
        await fresh.subscribe(("rs/ret", 0))
        m = await fresh.next_message(timeout=10)
        assert m.payload == b"kept" and m.retain
        await sub2.disconnect()
        await fresh.disconnect()
    finally:
        await b2.close()


# ---------------------------------------------------------------------------
# ADR 014: forward-compat records + quarantine-tolerant restore
# ---------------------------------------------------------------------------


def test_from_json_drops_unknown_keys_all_records():
    """A record written by a NEWER build restores after a downgrade:
    unknown keys drop instead of raising TypeError in cls(**d)."""
    c = json.loads(ClientRecord(client_id="c1").to_json())
    c["future_field"] = {"nested": True}
    assert ClientRecord.from_json(json.dumps(c)).client_id == "c1"

    s = json.loads(SubscriptionRecord(client_id="c1", filter="a/+").to_json())
    s["delivery_priority"] = 9
    assert SubscriptionRecord.from_json(json.dumps(s)).filter == "a/+"

    m = json.loads(MessageRecord(topic="t", payload=b"x").to_json())
    m["compression"] = "zstd"
    back = MessageRecord.from_json(json.dumps(m))
    assert back.topic == "t" and back.payload == b"x"


def test_restore_quarantines_torn_records_instead_of_aborting():
    store = MemoryStore()
    good = SubscriptionRecord(client_id="c1", filter="ok/#").to_json()
    store.put("subscriptions", "c1|ok/#", good)
    store.put("subscriptions", "c1|torn", '{"client_id": "c1", "fil')
    store.put("inflight", "c1|7", "\x00not json at all")
    hook = StorageHook(store)
    subs = hook.stored_subscriptions()
    assert [r.filter for r in subs] == ["ok/#"]
    assert hook.stored_inflight_messages() == []
    assert hook.quarantined == 2
    q = store.all("quarantine")
    assert "subscriptions|c1|torn" in q and "inflight|c1|7" in q
    # the torn originals are gone: the next boot doesn't re-trip
    assert "c1|torn" not in store.all("subscriptions")


def test_restore_fault_site_quarantines_one_record():
    store = MemoryStore()
    for i in range(3):
        store.put("retained", f"t/{i}",
                  MessageRecord(topic=f"t/{i}", payload=b"v").to_json())
    hook = StorageHook(store)
    faults.clear()
    try:
        faults.arm(faults.STORAGE_RESTORE, "raise", count=1)
        msgs = hook.stored_retained_messages()
    finally:
        faults.clear()
    assert len(msgs) == 2 and hook.quarantined == 1
    assert len(store.all("quarantine")) == 1


def test_boot_epoch_monotonic():
    store = MemoryStore()
    hook = StorageHook(store)
    first = hook.bump_boot_epoch()
    assert first >= 1_000_000_000_000      # wall-clock ms seed
    # a second boot off the same store is exactly +1, clock-independent
    assert StorageHook(store).bump_boot_epoch() == first + 1
    assert StorageHook(store).bump_boot_epoch() == first + 2


# ---------------------------------------------------------------------------
# ADR 014: write-behind journal
# ---------------------------------------------------------------------------


def test_journal_overlay_reads_and_group_commit():
    inner = GatedStore()
    inner.gate.clear()                     # hold the writer thread
    st = WriteBehindStore(inner, policy="batched", batch_ms=0)
    try:
        st.put("b", "k1", "v1")
        st.put("b", "k2", "v2")
        st.delete("b", "k2")
        st.put("b", "pre:a", "1")
        st.delete_prefix("b", "pre:")
        st.put("b", "pre:b", "2")          # re-put AFTER the prefix delete
        # reads see the pending journal overlaid on the (empty) backend
        assert st.get("b", "k1") == "v1"
        assert st.get("b", "k2") is None
        assert st.all("b") == {"k1": "v1", "pre:b": "2"}
        assert inner.all("b") == {}        # nothing committed yet
        inner.gate.set()
        assert st.flush(timeout=5.0)
        assert inner.all("b") == {"k1": "v1", "pre:b": "2"}
        assert st.commits >= 1 and st.ops_written >= 5
    finally:
        st.close()


def test_journal_coalesces_same_key_rewrites():
    inner = GatedStore()
    inner.gate.clear()
    st = WriteBehindStore(inner, policy="batched", batch_ms=0,
                          queue_bytes=1 << 20)
    try:
        for i in range(200):
            st.put("retained", "sensor/1", f"value-{i}")
        assert st.queue_depth == 1          # one queued op, latest value
        assert st.coalesced == 199
        assert st.get("retained", "sensor/1") == "value-199"
        inner.gate.set()
        assert st.flush(timeout=5.0)
        assert inner.get("retained", "sensor/1") == "value-199"
    finally:
        st.close()


def test_journal_watermark_overflow_counted():
    inner = GatedStore()
    inner.gate.clear()
    st = WriteBehindStore(inner, policy="batched", batch_ms=0,
                          queue_bytes=256)
    try:
        for i in range(20):
            st.put("b", f"k{i}", "x" * 64)
        assert st.over_watermark and st.overflows > 0
        inner.gate.set()
        assert st.flush(timeout=5.0)
        assert not st.over_watermark        # drained below the budget
    finally:
        st.close()


async def test_journal_durability_barrier_resolves_on_commit():
    inner = GatedStore()
    inner.gate.clear()
    st = WriteBehindStore(inner, policy="always")
    loop = asyncio.get_running_loop()
    try:
        assert st.barrier(loop) is None     # idle journal: no wait
        st.put("b", "k", "v")
        fut = st.barrier(loop)
        assert fut is not None
        await asyncio.sleep(0.05)
        assert not fut.done()               # backend gated: not durable
        inner.gate.set()
        await asyncio.wait_for(fut, timeout=5.0)
        assert inner.get("b", "k") == "v"   # durable BEFORE the barrier
    finally:
        st.close()


async def test_journal_breaker_opens_releases_barriers_and_recovers():
    """The storage degradation ladder end to end: consecutive commit
    failures trip the breaker (memory-backed writes, dirty flag, all
    barriers released), a half-open reprobe after backoff replays the
    parked journal, and the backend converges to every write."""
    inner = GatedStore()
    inner.fail = True
    st = WriteBehindStore(inner, policy="always", breaker_threshold=3,
                          backoff_s=0.05, backoff_max_s=0.2)
    loop = asyncio.get_running_loop()
    try:
        st.put("b", "k1", "v1")
        fut = st.barrier(loop)
        deadline = time.monotonic() + 5.0
        while st.breaker_state != BREAKER_OPEN:
            assert time.monotonic() < deadline, "breaker never opened"
            await asyncio.sleep(0.01)
        assert st.breaker_trips >= 1 and st.dirty
        # the pending barrier was released degraded, counted as such
        await asyncio.wait_for(fut, timeout=2.0)
        assert st.barriers_released_degraded >= 1
        # degraded mode: writes still land (parked journal), reads see
        # them, and new barriers don't wait
        st.put("b", "k2", "v2")
        assert st.get("b", "k2") == "v2"
        assert st.barrier(loop) is None
        assert st.commit_failures >= 3
        # heal the backend: the half-open reprobe replays everything
        inner.fail = False
        deadline = time.monotonic() + 5.0
        while st.breaker_state != BREAKER_CLOSED:
            assert time.monotonic() < deadline, "breaker never recovered"
            await asyncio.sleep(0.01)
        assert st.flush(timeout=5.0)
        assert inner.all("b") == {"k1": "v1", "k2": "v2"}
        assert st.breaker_recoveries == 1
        assert st.degraded_seconds > 0
    finally:
        st.close()


def test_journal_put_fault_site_counts_and_drops():
    inner = GatedStore()
    st = WriteBehindStore(inner, policy="batched", batch_ms=0)
    faults.clear()
    try:
        faults.arm(faults.STORAGE_PUT, "raise", count=1)
        st.put("b", "lost", "v")
        st.put("b", "kept", "v")
        assert st.put_failures == 1 and st.dirty
        assert st.flush(timeout=5.0)
        assert inner.all("b") == {"kept": "v"}
    finally:
        faults.clear()
        st.close()


def test_journal_commit_fault_site_parks_then_replays():
    inner = GatedStore()
    st = WriteBehindStore(inner, policy="batched", batch_ms=0,
                          breaker_threshold=10)
    faults.clear()
    try:
        faults.arm(faults.STORAGE_COMMIT, "raise", count=2)
        st.put("b", "k", "v")
        assert st.flush(timeout=5.0)        # retried past the 2 failures
        assert inner.get("b", "k") == "v"
        assert st.commit_failures == 2 and st.dirty
        assert st.breaker_state == BREAKER_CLOSED
    finally:
        faults.clear()
        st.close()


def test_journal_rejects_unknown_policy():
    with pytest.raises(ValueError):
        WriteBehindStore(MemoryStore(), policy="sometimes")


# ---------------------------------------------------------------------------
# ADR 014: SQLite hardening
# ---------------------------------------------------------------------------


def test_sqlite_synchronous_pragma_follows_policy(tmp_path):
    from maxmq_tpu.hooks.journal import SQLITE_SYNC_BY_POLICY
    for policy, expect in (("always", 2), ("batched", 2), ("off", 0)):
        st = SQLiteStore(str(tmp_path / f"{policy}.db"),
                         synchronous=SQLITE_SYNC_BY_POLICY[policy])
        level = st._conn.execute("PRAGMA synchronous").fetchone()[0]
        busy = st._conn.execute("PRAGMA busy_timeout").fetchone()[0]
        st.close()
        assert level == expect and busy == 5000


def test_sqlite_corrupt_file_moved_aside_and_recreated(tmp_path):
    path = str(tmp_path / "bad.db")
    with open(path, "wb") as f:                 # not a SQLite file
        f.write(b"SQLite format 3\x00" + b"\xde\xad\xbe\xef" * 512)
    st = SQLiteStore(path)
    try:
        assert st.corruptions == 1
        assert (tmp_path / "bad.db.corrupt-1").exists()
        st.put("b", "k", "v")                   # fresh file serves writes
        assert st.get("b", "k") == "v"
    finally:
        st.close()
    # a second corruption on the same path picks the next aside slot
    with open(path, "wb") as f:
        f.write(b"garbage" * 100)
    st2 = SQLiteStore(path)
    try:
        assert st2.corruptions == 1
        assert (tmp_path / "bad.db.corrupt-2").exists()
    finally:
        st2.close()


def test_sqlite_apply_batch_single_transaction(tmp_path):
    st = SQLiteStore(str(tmp_path / "batch.db"))
    try:
        st.apply_batch([("put", "b", "k1", "v1"),
                        ("put", "b", "pre:x", "1"),
                        ("delete_prefix", "b", "pre:", None),
                        ("put", "b", "k2", "v2"),
                        ("delete", "b", "k1", None)])
        assert st.all("b") == {"k2": "v2"}
    finally:
        st.close()


# ---------------------------------------------------------------------------
# ADR 014: shed policy + redundant-rewrite elision
# ---------------------------------------------------------------------------


class _StubOverload:
    def __init__(self, shedding):
        self.shedding = shedding
        self.records_spliced = self.records_built = 0


class _StubServer:
    def __init__(self, shedding):
        self.overload = _StubOverload(shedding)


class _StubClient:
    def __init__(self, cid="c1", shedding=False):
        self.id = cid
        self.server = _StubServer(shedding)
        self.inflight = Inflight()


def _retain_packet(topic="shed/t", qos=0):
    return Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=qos, retain=True),
                  topic=topic, payload=b"v", created=1.0)


def test_hook_sheds_qos0_retained_rewrites_past_watermark():
    inner = GatedStore()
    inner.gate.clear()                      # wedge the backend
    st = WriteBehindStore(inner, policy="batched", batch_ms=0,
                          queue_bytes=128)
    hook = StorageHook(st)
    try:
        healthy0 = _StubClient(shedding=False)
        for i in range(10):                 # drive past the watermark
            hook.on_retain_message(healthy0, _retain_packet(f"t/{i}"), 1)
        assert st.over_watermark and hook.journal_sheds == 0
        shedding = _StubClient(shedding=True)
        before = st.queue_depth
        hook.on_retain_message(shedding, _retain_packet("t/more"), 1)
        assert hook.journal_sheds == 1 and st.queue_depth == before
        # QoS1 retained writes are never shed — durability-relevant
        hook.on_retain_message(shedding, _retain_packet("t/q1", qos=1), 1)
        assert st.queue_depth == before + 1
        # not shedding (ADR-012 ladder healthy): writes proceed even
        # past the watermark, only counted as overflow
        healthy = _StubClient(shedding=False)
        hook.on_retain_message(healthy, _retain_packet("t/h"), 1)
        assert st.queue_depth == before + 2 and hook.journal_sheds == 1
    finally:
        inner.gate.set()
        st.close()


def test_hook_skips_redundant_inflight_resend_rewrites():
    store = MemoryStore()
    hook = StorageHook(store)
    client = _StubClient("sub1")
    p = Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=1), topic="a/b",
               payload=b"m", packet_id=5, created=1.0)
    client.inflight.set(p)
    hook.on_qos_publish(client, p, 1.0, 0)
    assert len(store.all("inflight")) == 1
    assert client.inflight.stored(5)
    # resend of the already-persisted record: elided
    hook.on_qos_publish(client, p, 2.0, 1)
    assert hook.rewrites_skipped == 1
    # a RESEND of a record the store never saw still writes
    q = Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=1), topic="a/c",
               payload=b"m2", packet_id=6, created=1.0)
    client.inflight.set(q)
    hook.on_qos_publish(client, q, 2.0, 1)
    assert len(store.all("inflight")) == 2
    assert hook.rewrites_skipped == 1
    # ack clears the marker with the entry
    client.inflight.delete(5)
    assert not client.inflight.stored(5)


# ---------------------------------------------------------------------------
# ADR 014: full broker restore THROUGH the journal
# ---------------------------------------------------------------------------


async def test_full_restore_through_write_behind_journal(tmp_path):
    """The PR-ADR-014 pipeline end to end in-process: broker writes ride
    the journal (policy=always → acks barriered), close() flushes, and
    a second broker restores sessions/subs/retained/inflight from the
    same SQLite file while boot_epoch strictly increases."""
    path = str(tmp_path / "journal.db")
    epochs = []

    def build():
        b = Broker(BrokerOptions(capabilities=Capabilities(
            sys_topic_interval=0)))
        b.add_hook(AllowHook())
        b.add_hook(StorageHook(WriteBehindStore(
            SQLiteStore(path), policy="always")))
        b.add_listener(TCPListener("t", "127.0.0.1:0"))
        return b

    b1 = build()
    await b1.serve()
    epochs.append(b1.boot_epoch)
    port = b1.listeners.get("t")._server.sockets[0].getsockname()[1]
    sub = MQTTClient(client_id="wj-sub", clean_start=False)
    await sub.connect("127.0.0.1", port)
    await sub.subscribe(("wj/x", 1))
    await sub.disconnect()
    pub = MQTTClient(client_id="wj-pub")
    await pub.connect("127.0.0.1", port)
    await pub.publish("wj/x", b"queued", qos=1)     # barriered PUBACK
    await pub.publish("wj/ret", b"kept", qos=1, retain=True)
    await pub.disconnect()
    assert b1.storage_barrier_waits > 0             # barrier actually used
    await b1.close()

    b2 = build()
    await b2.serve()
    epochs.append(b2.boot_epoch)
    port = b2.listeners.get("t")._server.sockets[0].getsockname()[1]
    try:
        sub2 = MQTTClient(client_id="wj-sub", clean_start=False)
        await sub2.connect("127.0.0.1", port)
        assert sub2.connack.session_present is True
        m = await sub2.next_message(timeout=10)
        assert m.payload == b"queued"
        fresh = MQTTClient(client_id="wj-fresh")
        await fresh.connect("127.0.0.1", port)
        await fresh.subscribe(("wj/ret", 0))
        m = await fresh.next_message(timeout=10)
        assert m.payload == b"kept" and m.retain
        await sub2.disconnect()
        await fresh.disconnect()
    finally:
        await b2.close()
    assert epochs[1] > epochs[0]


def test_journal_close_with_dead_backend_exits_fast_and_loudly():
    """close() against a backend that never recovers: one final reprobe,
    then the writer exits — parked ops are reported lost (dirty), and
    the thread never spins past the join deadline."""
    inner = GatedStore()
    inner.fail = True
    st = WriteBehindStore(inner, policy="batched", batch_ms=0,
                          breaker_threshold=1, backoff_s=30.0)
    st.put("b", "k", "v")
    deadline = time.monotonic() + 5.0
    while st.breaker_state != BREAKER_OPEN:
        assert time.monotonic() < deadline, "breaker never opened"
        time.sleep(0.01)
    t0 = time.monotonic()
    st.close()
    assert time.monotonic() - t0 < 9.0      # no 30s-backoff wait
    assert st.dirty and not st._thread.is_alive()
