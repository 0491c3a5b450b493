"""The fan-out walks the sessions that exist (ADR 007, resolve): a
broker whose table is mostly stored subscriptions WITHOUT a session must
deliver exactly what the trie says, filtered by the client registry, and
keep every rule that runs after the lookup: $share rotation and
exactly-once, offline QoS 1 into inflight and the journal hook,
NoLocal, the content plane's skip set, and the two counters that say
what was walked and what was kept."""

import asyncio
import json

import pytest

from maxmq_tpu.hooks.base import Hook
from maxmq_tpu.matching import TopicIndex
from maxmq_tpu.protocol import Subscription

from test_broker_system import connect, running_broker

MODES = ["trie", "sig_intents", "sig_sets"]


def attach(broker, mode: str) -> None:
    """The result type the fan-out is handed: the trie's SubscriberSet,
    the native decode's DeliveryIntents, or its SubscriberSets."""
    if mode == "trie":
        return
    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.matching.sig import SigEngine
    from maxmq_tpu.native import decode_module
    mod = decode_module()
    if mod is None or not hasattr(mod, "DeliveryIntents"):
        pytest.skip("maxmq_decode extension unavailable")
    eng = SigEngine(broker.topics)
    eng.emit_intents = mode == "sig_intents"
    eng.route_small = False         # the device path, not the ADR-008 trie
    broker.attach_matcher(MicroBatcher(eng, window_us=0))


def store_ghosts(broker, n: int = 240) -> None:
    """Stored subscriptions whose clients have no session: what a
    restored fleet table is mostly made of."""
    shapes = ["fl/#", "fl/+/t", "fl/a/#", "$share/g/fl/#", "$share/h/fl/a/t"]
    for i in range(n):
        broker.topics.subscribe(
            f"ghost{i:03d}",
            Subscription(filter=shapes[i % len(shapes)], qos=i % 3))


async def drain(client, expect: int = 0, settle: float = 0.3) -> list:
    """``expect`` messages (a first match may compile: be patient), then
    whatever else trickles in within ``settle``."""
    out = [await client.next_message(timeout=20) for _ in range(expect)]
    while True:
        try:
            out.append(await client.next_message(timeout=settle))
        except asyncio.TimeoutError:
            return out


@pytest.mark.parametrize("mode", MODES)
async def test_delivered_sets_are_the_tries_filtered_by_the_registry(mode):
    async with running_broker() as broker:
        store_ghosts(broker)
        a = await connect(broker, "live-a")
        await a.subscribe(("fl/#", 1))
        b = await connect(broker, "live-b")
        await b.subscribe(("fl/+/t", 0), ("other/x", 0))
        c = await connect(broker, "live-c")
        await c.subscribe(("nothing/here", 0))
        attach(broker, mode)
        pub = await connect(broker, "pub")
        topics = ["fl/a/t", "fl/b/t", "fl/a/q", "fl", "other/x", "miss/x"]
        over = broker.overload
        m0, r0 = over.fanout_matched, over.fanout_resolved
        want: dict = {"live-a": [], "live-b": [], "live-c": []}
        matched = resolved = 0
        for i, topic in enumerate(topics):
            await pub.publish(topic, f"m{i}".encode(), qos=1)
            truth = broker.topics.subscribers(topic)
            matched += len(truth)
            for cid in truth.subscriptions:
                if broker.clients.get(cid) is not None:
                    want[cid].append((topic, f"m{i}".encode()))
                    resolved += 1
            resolved += sum(broker.clients.get(cid) is not None
                            for m in truth.shared.values() for cid in m)
        for client in (a, b, c):
            mine = want[client.client_id]
            got = [(m.topic, m.payload)
                   for m in await drain(client, len(mine))]
            assert got == mine, client.client_id
        assert want["live-a"] and want["live-b"] and not want["live-c"]
        # walked: every entry the results held; kept: those with a session
        assert over.fanout_matched - m0 == matched
        assert over.fanout_resolved - r0 == resolved
        assert matched > 50 * resolved


@pytest.mark.parametrize("mode", MODES)
async def test_share_rotation_with_unregistered_members(mode):
    """A $share group of live and stored-only members rotates over the
    live ones exactly as select_shared does when asked for every key,
    and a group with no live member is never picked and moves no
    cursor."""
    filt = "$share/g/job/#"
    async with running_broker() as broker:
        for cid in ("m1-ghost", "m3-ghost", "m5-ghost"):
            broker.topics.subscribe(cid, Subscription(filter=filt, qos=1))
        for cid in ("n1-ghost", "n2-ghost"):
            broker.topics.subscribe(
                cid, Subscription(filter="$share/none/job/#", qos=1))
        store_ghosts(broker, 60)
        live = {}
        for cid in ("m2-live", "m4-live"):
            live[cid] = await connect(broker, cid)
            await live[cid].subscribe((filt, 1))
        attach(broker, mode)
        pub = await connect(broker, "pub")
        oracle = TopicIndex()
        candidates = broker.topics.subscribers("job/1").shared[("g", filt)]
        assert len(candidates) == 5
        picks, cursors = [], []
        for i in range(20):
            await pub.publish("job/1", f"j{i}".encode(), qos=1)
            want_cid, _sub = oracle.select_shared(
                "g", filt, candidates, alive=live.__contains__)
            msg = await live[want_cid].next_message(timeout=5)
            assert msg.payload == f"j{i}".encode()
            picks.append(want_cid)
            cursors.append(dict(broker.topics._share_cursor))
            assert cursors[-1] == oracle._share_cursor
        assert picks == ["m2-live", "m4-live"] * 10
        assert all(("none", "$share/none/job/#") not in c for c in cursors)
        for client in live.values():        # exactly once: nothing more
            assert await drain(client, settle=0.2) == []


class _QosJournal(Hook):
    """Stands where the storage hook stands: sees every QoS > 0
    delivery that enters a session's inflight window."""

    def __init__(self) -> None:
        super().__init__()
        self.seen: list = []

    def on_qos_publish(self, client, packet, sent, resends) -> None:
        self.seen.append((client.id, packet.topic, packet.fixed.qos))


@pytest.mark.parametrize("mode", MODES)
async def test_offline_session_still_gets_qos1_into_inflight(mode):
    """A session that exists and is offline is IN the registry: it is
    resolved and goes down the path it always went (QoS 0 not queued,
    QoS 1 into inflight and past the journal hook)."""
    async with running_broker() as broker:
        journal = _QosJournal()
        broker.add_hook(journal)
        store_ghosts(broker)
        s = await connect(broker, "sleeper", clean_start=False)
        await s.subscribe(("fl/a/t", 1))
        await s.disconnect()
        await asyncio.sleep(0.05)
        session = broker.clients.get("sleeper")
        assert session is not None and session.closed
        attach(broker, mode)
        pub = await connect(broker, "pub")
        await pub.publish("fl/a/t", b"q0", qos=0)
        await pub.publish("fl/a/t", b"q1", qos=1)
        for _ in range(1000):       # a first match may compile
            if len(session.inflight):
                break
            await asyncio.sleep(0.02)
        held = session.inflight.all()
        assert [(p.topic, p.payload, p.fixed.qos) for p in held] == \
            [("fl/a/t", b"q1", 1)]
        assert journal.seen == [("sleeper", "fl/a/t", 1)]
        s2 = await connect(broker, "sleeper", clean_start=False)
        assert s2.connack.session_present
        msg = await s2.next_message(timeout=5)
        assert (msg.payload, msg.qos) == (b"q1", 1)


@pytest.mark.parametrize("mode", MODES)
async def test_no_local_and_content_skip_hold_after_the_lookup(mode):
    async with running_broker() as broker:
        store_ghosts(broker)
        nl = await connect(broker, "nl", version=5)
        await nl.subscribe(("fl/a/t", 0), no_local=True)
        other = await connect(broker, "other", version=5)
        await other.subscribe(("fl/a/t", 0))
        gated = await connect(broker, "gated")
        await gated.subscribe(("fl/+/t?$expr=payload.temp>30", 0))
        attach(broker, mode)
        await nl.publish("fl/a/t", json.dumps({"temp": 10}).encode())
        await nl.publish("fl/a/t", json.dumps({"temp": 40}).encode())
        temps = [json.loads(m.payload)["temp"]
                 for m in await drain(other, 2)]
        assert temps == [10, 40]
        assert await drain(nl, settle=0.2) == []        # NoLocal
        temps = [json.loads(m.payload)["temp"]
                 for m in await drain(gated, 1)]
        assert temps == [40]                            # _content_skip


async def test_fanout_counters_are_exported():
    from maxmq_tpu.metrics import Registry, register_broker_metrics
    async with running_broker() as broker:
        store_ghosts(broker, 50)
        a = await connect(broker, "live-a")
        await a.subscribe(("fl/#", 0))
        pub = await connect(broker, "pub")
        await pub.publish("fl/a/t", b"x", qos=1)
        await a.next_message(timeout=5)
        reg = Registry()
        register_broker_metrics(reg, broker)
        text = reg.expose()
        truth = broker.topics.subscribers("fl/a/t")
        assert f"maxmq_broker_fanout_matched_total {len(truth)}" in text
        assert "maxmq_broker_fanout_resolved_total 1" in text
