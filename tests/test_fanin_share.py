"""Fan-in to one ``$share`` group through a served broker over TCP: the
deployment ``fleet-fanin-500`` (perfbench/configs/fleet-fanin-500.json;
Open MQTT Benchmark Suite, ``fanin-50K-500-50K-50K``) at a size the CPU
carries: a few thousand stored corpus filters, 50 persistent sessions
that all hold ``$share/ingest/fleet/telemetry/#`` at QoS 1, and 200
publisher connections with one QoS 1 message each in flight. The broker
is ``bootstrap.run_server``'s on a store written the way the benchmark's
harness writes it, with ``matcher`` = ``trie`` and ``sig`` (supervised,
the CPU backend), so a ``$share`` key of 50 candidates is resolved and
picked from on both answer paths. What arrived is held against the
benchmark's plain reference (``perfbench/reference.py``)."""

from __future__ import annotations

import asyncio
import contextlib
import io
import os
import sys

import pytest

from maxmq_tpu.bootstrap import run_server
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.utils.config import Config
from maxmq_tpu.utils.logger import Logger

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
sys.path.insert(0, BENCH)

import generators  # noqa: E402  (perfbench's: recipes found by name)
from reference import Reference  # noqa: E402

from test_fanout_wide import write_store  # noqa: E402

SEED = 3_000_000_031        # more than 32 signed bits hold
STORED, MEMBERS, DEVICES, PUBLISHERS, MESSAGES = 3000, 50, 400, 200, 3
GROUP = "ingest"
MATCHERS = ["trie", "sig"]


class Served:
    """What a case holds of its broker: the live plan, the reference,
    the resumed sessions and the publisher connections."""

    def __init__(self, broker, plan, members, hits) -> None:
        self.broker, self.plan, self.members, self.hits = \
            broker, plan, members, hits
        self.ref = Reference(plan)
        self.port = broker.listeners.get("tcp")._server.sockets[0] \
            .getsockname()[1]
        self.subs: dict[str, MQTTClient] = {}
        self.pubs: list[MQTTClient] = []

    async def connect(self, publishers: int) -> None:
        # the sessions come back with clean_start = 0 and SUBSCRIBE nothing
        for cid in self.plan:
            c = self.subs[cid] = MQTTClient(client_id=cid, clean_start=False)
            await c.connect("127.0.0.1", self.port)
            assert c.session_present is True
        for p in range(publishers):
            c = MQTTClient(client_id=f"load-p{p}")
            await c.connect("127.0.0.1", self.port)
            self.pubs.append(c)

    async def publish_all(self, rounds: int, first: int = 0) -> list:
        """Every publisher sends ``rounds`` QoS 1 messages, each after
        the PUBACK of the one before (one in flight a connection), on
        device topics; the payload is ``<publisher>:<seq>``. Returns
        what was sent as (topic, payload)."""
        sent = []

        async def one(p: int, client: MQTTClient) -> None:
            for k in range(first, first + rounds):
                topic = self.hits[(p * 7 + k * 13) % len(self.hits)]
                sent.append((topic, b"%d:%d" % (p, k)))
                await client.publish(topic, sent[-1][1], qos=1, timeout=60)

        await asyncio.wait_for(asyncio.gather(
            *(one(p, c) for p, c in enumerate(self.pubs))), 240)
        return sent

    async def collect(self, want: int, among=None) -> dict:
        """member id -> its messages, once ``want`` have arrived in all."""
        subs = {cid: self.subs[cid] for cid in (among or self.subs)}
        for _ in range(1200):
            if sum(c.messages.qsize() for c in subs.values()) >= want:
                break
            await asyncio.sleep(0.05)
        got = {}
        for cid, c in subs.items():
            got[cid] = []
            while not c.messages.empty():
                got[cid].append(c.messages.get_nowait())
        return got

    async def settled(self, deliveries: int) -> None:
        """Every delivery acknowledged: nothing in flight, and the
        journal holds no inflight record."""
        broker = self.broker
        for _ in range(400):
            if (broker.overload.fanout_acks >= deliveries
                    and not broker.info.inflight):
                break
            await asyncio.sleep(0.05)
        assert broker.overload.fanout_acks == deliveries
        assert broker.info.inflight == 0
        assert all(len(broker.clients.get(cid).inflight) == 0
                   for cid in self.plan)
        journal = broker._journal
        assert journal.flush(timeout=30)
        assert journal.all("inflight") == {}


@contextlib.asynccontextmanager
async def served(tmp_path, matcher: str, publishers: int = PUBLISHERS,
                 **conf):
    stored = generators.corpus(STORED, SEED, share_frac=0.1)
    plan, groups, hits = generators.find("fanin_live")(
        SEED, subscribers=MEMBERS, devices=DEVICES)
    assert list(groups) == [GROUP] and groups[GROUP] == list(plan)
    path = str(tmp_path / "store.db")
    n_subs = write_store(path, stored, plan)
    config = Config(mqtt_tcp_address="127.0.0.1:0", metrics_enabled=False,
                    matcher=matcher, mqtt_sys_topic_interval=0,
                    log_level="warn", storage_backend="sqlite",
                    storage_path=path, **conf)
    ready, stop, built = asyncio.Event(), asyncio.Event(), []
    server = asyncio.ensure_future(run_server(
        config, Logger(out=io.StringIO(), fmt="json"), ready=ready,
        stop=stop, broker_out=built))
    s = None
    try:
        await asyncio.wait_for(ready.wait(), timeout=120)
        broker = built[0]
        assert broker.topics.subscription_count == n_subs
        assert (broker.matcher is not None) == (matcher == "sig")
        s = Served(broker, plan, groups[GROUP], hits)
        await s.connect(publishers)
        yield s
        if matcher == "sig":    # no answer came from a path that failed
            assert (broker.matcher.error_fallbacks,
                    broker.matcher_degrades) == (0, 0)
    finally:
        if s is not None:
            for c in list(s.subs.values()) + s.pubs:
                await c.close()
        stop.set()
        await asyncio.wait_for(server, timeout=120)


def check_against_reference(s: Served, sent: list, got: dict,
                            members: list) -> None:
    """Each message reached exactly one member of the group, the one
    receiver the reference allows; nothing came twice or unasked; each
    publisher's messages are in order at every member; the members'
    counts are within one of each other (round robin)."""
    for topic, _payload in sent[:20] + sent[-20:]:
        plain, shared = s.ref.receivers(topic)
        assert plain == {} and shared == {GROUP: {m: 1 for m in s.members}}
    have = [(m.topic, m.payload) for msgs in got.values() for m in msgs]
    assert sorted(have) == sorted(sent)             # once each, no more
    assert set(got) <= set(members)
    assert all(m.qos == 1 and not m.retain
               for msgs in got.values() for m in msgs)
    for cid, msgs in got.items():
        last: dict = {}
        for m in msgs:
            p, k = (int(x) for x in m.payload.split(b":"))
            assert k > last.get(p, -1), (cid, p, k)
            last[p] = k
    counts = [len(got.get(cid, ())) for cid in members]
    assert max(counts) - min(counts) <= 1, counts


@pytest.mark.parametrize("matcher", MATCHERS)
async def test_each_message_reaches_one_member_round_robin(tmp_path, matcher):
    async with served(tmp_path, matcher) as s:
        over, info, index = s.broker.overload, s.broker.info, s.broker.topics
        before = (over.share_picks, over.share_candidates, over.read_chunks,
                  info.packets_received,
                  index.share_orders_reused + index.share_orders_sorted)
        sent = await s.publish_all(MESSAGES)
        n = PUBLISHERS * MESSAGES
        assert len(sent) == n
        got = await s.collect(n)
        check_against_reference(s, sent, got, s.members)
        await s.settled(n)
        assert all(c.messages.empty() for c in s.pubs)
        # one pick a message from a set of 50; one chunk a PUBLISH (one
        # in flight a socket), a PUBACK's chunk may hold a second one
        assert over.share_picks - before[0] == n
        assert over.share_candidates - before[1] == n * MEMBERS
        assert over.share_widest == MEMBERS
        # a pick reused the order its key kept, or sorted one to keep
        assert (index.share_orders_reused + index.share_orders_sorted
                - before[4]) == n
        packets = info.packets_received - before[3]
        assert packets == 2 * n
        assert n < over.read_chunks - before[2] <= packets
        assert s.broker.tracer.allocations == 0
        if matcher == "sig":
            # the group's key as the engine's own two forms give it
            # (whichever of trie walk and host probe the served batches
            # took): one key, 50 candidates, every one with a session
            engine = s.broker.matcher.inner.engine
            for ask in (engine.subscribers_host_batch,
                        engine.subscribers_fixed_batch):
                (result,) = ask([s.hits[5]])
                pairs, shared, matched, resolved = \
                    s.broker.clients.resolve(result)
                assert not pairs and matched == resolved == MEMBERS
                ((key, members),) = shared.items()
                assert key == (GROUP, f"$share/{GROUP}/fleet/telemetry/#")
                assert sorted(members) == sorted(s.members)
                # the native rows hand out one map a row: sorted once a
                # key (at most: a served batch may have seen it first),
                # and every pick after it reuses the kept order
                reused, ordered = (index.share_orders_reused,
                                   index.share_orders_sorted)
                picks = [index.select_shared(*key, ask([s.hits[k]])[0]
                                             .shared[key])[0]
                         for k in range(6)]
                assert index.share_orders_sorted - ordered <= 1
                assert index.share_orders_reused - reused >= 5
                at = sorted(members).index(picks[0])
                assert picks == (sorted(members) * 2)[at:at + 6]


@pytest.mark.parametrize("matcher", MATCHERS)
async def test_a_closed_member_is_skipped_and_nothing_is_lost(tmp_path,
                                                              matcher):
    async with served(tmp_path, matcher, publishers=40) as s:
        first = await s.publish_all(2)
        got = await s.collect(len(first))
        check_against_reference(s, first, got, s.members)
        await s.settled(len(first))
        gone = s.members[7]
        await s.subs[gone].close()          # the socket is cut mid-stream
        for _ in range(400):
            if s.broker.clients.get(gone).closed:
                break
            await asyncio.sleep(0.05)
        assert s.broker.clients.get(gone).closed
        second = await s.publish_all(3, first=2)
        left = [m for m in s.members if m != gone]
        got = await s.collect(len(second), among=left)
        check_against_reference(s, second, got, left)
        # nothing was parked for the member that left
        assert len(s.broker.clients.get(gone).inflight) == 0
        await s.settled(len(first) + len(second))


@pytest.mark.parametrize("matcher", MATCHERS)
async def test_share_pick_span_only_where_a_share_key_was(tmp_path, matcher):
    async with served(tmp_path, matcher, publishers=2, trace_sample_n=1,
                      trace_slow_ms=0.0, trace_ring=256) as s:
        tracer = s.broker.tracer
        plain = MQTTClient(client_id="watcher")
        await plain.connect("127.0.0.1", s.port)
        await plain.subscribe(("audit/#", 1))
        s.pubs.append(plain)
        await s.pubs[0].publish(s.hits[3], b"0:0", qos=1)
        await s.pubs[1].publish("audit/login", b"1:0", qos=1)
        await s.pubs[1].publish("nobody/listens", b"1:1", qos=1)
        await s.collect(1)
        assert (await plain.next_message(timeout=30)).topic == "audit/login"
        by_topic = {}
        for _ in range(200):
            by_topic = {e["topic"]: e for e in tracer.report()["entries"]}
            if len(by_topic) >= 3:
                break
            await asyncio.sleep(0.05)
        spans = {t: {sp["stage"]: sp for sp in e["spans"]}
                 for t, e in by_topic.items()}
        picked = spans[s.hits[3]]
        assert picked["share_pick"]["parent"] == "fanout"
        # the picks lie inside the fan-out and are no part of the sum
        fan, pick = picked["fanout"], picked["share_pick"]
        assert fan["off_us"] <= pick["off_us"]
        assert pick["off_us"] + pick["dur_us"] <= \
            fan["off_us"] + fan["dur_us"] + 1
        assert "fanout" in spans["audit/login"]
        for topic in ("audit/login", "nobody/listens"):
            assert "share_pick" not in spans[topic]
        assert tracer.stage_hist["share_pick"].count == 1
        assert s.broker.overload.share_picks == 1


@pytest.mark.parametrize("matcher", MATCHERS)
async def test_sampling_off_the_picks_allocate_nothing(tmp_path, matcher):
    async with served(tmp_path, matcher, publishers=20) as s:
        tracer = s.broker.tracer
        assert tracer.sample_n == 0
        sent = await s.publish_all(2)
        got = await s.collect(len(sent))
        check_against_reference(s, sent, got, s.members)
        assert s.broker.overload.share_picks == len(sent)
        index = s.broker.topics
        assert (index.share_orders_reused + index.share_orders_sorted
                == len(sent))
        assert tracer.allocations == 0 and tracer.sampled == 0
        assert tracer.stage_hist["share_pick"].count == 0
        assert tracer.report()["entries"] == []


def test_share_order_counters_move_a_pick_and_once_a_map():
    """Reused rises a pick, sorted once a map a key: through the
    broker's own resolve and picks, on one result asked about again and
    again (what a native row's is) and then on a fresh walk's."""
    from types import SimpleNamespace

    from maxmq_tpu.broker import Broker, BrokerOptions
    from maxmq_tpu.protocol import Subscription
    from maxmq_tpu.protocol.codec import FixedHeader, PacketType
    from maxmq_tpu.protocol.packets import Packet
    broker = Broker(BrokerOptions())
    index, over = broker.topics, broker.overload
    filt = f"$share/{GROUP}/fleet/telemetry/#"
    ids = [f"ingest-{i}" for i in range(MEMBERS)]
    for cid in ids:
        index.subscribe(cid, Subscription(filter=filt, qos=1))
        broker.clients.add(SimpleNamespace(id=cid, closed=False))
    packet = Packet(fixed=FixedHeader(type=PacketType.PUBLISH),
                    topic="fleet/telemetry/dev-1", payload=b"x")
    result = index.subscribers(packet.topic)
    picked = []
    for k in range(1, 11):
        _pairs, shared, matched, resolved = broker.clients.resolve(result)
        assert (matched, resolved) == (MEMBERS, MEMBERS)
        picked += broker._pick_shared(shared, packet)
        assert (index.share_orders_sorted, index.share_orders_reused,
                over.share_picks) == (1, k - 1, k)
    assert picked == sorted(ids)[:10]
    fresh = index.subscribers(packet.topic)     # an equal map, another dict
    _pairs, shared, _m, _r = broker.clients.resolve(fresh)
    assert list(broker._pick_shared(shared, packet)) == [sorted(ids)[10]]
    assert (index.share_orders_sorted, index.share_orders_reused) == (2, 9)
    # a session ends: the count is taken again, the order is not
    broker.clients.delete(ids[0])
    assert broker.clients.resolve(fresh)[2:] == (MEMBERS, MEMBERS - 1)
    assert list(broker._pick_shared(shared, packet)) == [sorted(ids)[11]]
    assert (index.share_orders_sorted, index.share_orders_reused) == (2, 10)


def test_share_and_chunk_counters_exported():
    from maxmq_tpu.broker import Broker, BrokerOptions
    from maxmq_tpu.metrics import Registry, register_broker_metrics
    broker = Broker(BrokerOptions())
    over = broker.overload
    over.share_picks, over.share_candidates = 70_000, 35_000_000
    over.share_widest, over.read_chunks = 500, 140_000
    broker.topics.share_orders_reused = 69_999
    broker.topics.share_orders_sorted = 1
    reg = Registry()
    register_broker_metrics(reg, broker)
    text = reg.expose()
    assert "maxmq_broker_share_picks_total 70000" in text
    assert "maxmq_broker_share_candidates_total 35000000" in text
    assert "maxmq_broker_share_widest 500" in text
    assert "maxmq_broker_read_chunks_total 140000" in text
    assert "maxmq_broker_share_orders_reused_total 69999" in text
    assert "maxmq_broker_share_orders_sorted_total 1" in text


for _case in (test_each_message_reaches_one_member_round_robin,
              test_a_closed_member_is_skipped_and_nothing_is_lost,
              test_share_pick_span_only_where_a_share_key_was,
              test_sampling_off_the_picks_allocate_nothing):
    _case._async_timeout = 600
