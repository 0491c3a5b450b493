"""A wide QoS 1 broadcast through a served broker over TCP: the deployment
``fleet-fanout-1k`` (perfbench/configs/fleet-fanout-1k.json; Open MQTT
Benchmark Suite, ``fanout-5-1000-5-250K``) at a size the CPU carries: a
few thousand stored corpus filters, 200 persistent sessions that each
hold the five broadcast topics at QoS 1, five publishers. The broker is
``bootstrap.run_server``'s on a store written the way the benchmark's
harness writes it, with ``matcher`` = ``trie`` and ``sig`` (the CPU
backend), so that one filter's fat row of 200 entries (ADR 007) is
decoded and resolved on both answer paths. What arrived is held against
the benchmark's plain reference (``perfbench/reference.py``: MQTT 4.7
written straight down, no module of the program)."""

from __future__ import annotations

import asyncio
import io
import os
import random
import sys

import pytest

from maxmq_tpu.bootstrap import run_server
from maxmq_tpu.hooks.storage import (ClientRecord, SQLiteStore,
                                     SubscriptionRecord)
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.utils.config import Config
from maxmq_tpu.utils.logger import Logger

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
sys.path.insert(0, BENCH)

import generators  # noqa: E402  (perfbench's: recipes found by name)
from reference import Reference  # noqa: E402

SEED = 3_000_000_029        # more than 32 signed bits hold
STORED, SESSIONS, TOPICS, PUBLISHERS, MESSAGES = 3000, 200, 5, 5, 10


def write_store(path: str, stored: list, plan: dict) -> int:
    """The harness's store: one client ``cl-<i>`` a stored filter at QoS
    ``i % 3`` with no session record, and the live sessions as
    persistent ones (a ClientRecord and their SubscriptionRecords)."""
    store = SQLiteStore(path, synchronous="OFF")
    ops = [("put", "subscriptions", f"cl-{i}|{f}",
            SubscriptionRecord(client_id=f"cl-{i}", filter=f,
                               qos=i % 3).to_json())
           for i, f in enumerate(stored)]
    for cid, subs in plan.items():
        ops.append(("put", "clients", cid, ClientRecord(
            client_id=cid, listener="tcp", clean=False).to_json()))
        ops += [("put", "subscriptions", f"{cid}|{f}",
                 SubscriptionRecord(client_id=cid, filter=f,
                                    qos=q).to_json()) for f, q in subs]
    store.apply_batch(ops)
    store.close()
    return len(stored) + sum(len(v) for v in plan.values())


def traffic(hits: list) -> list[list[tuple[str, bytes]]]:
    """Each publisher's messages, in its order: broadcasts and fresh
    corpus topics, every payload ``<publisher>:<seq>``. Every broadcast
    topic is sent by somebody."""
    out = []
    for p in range(PUBLISHERS):
        rng = random.Random(SEED + p)
        topics = [hits[(p + k) % TOPICS] if k % 2 == 0
                  else generators.corpus_topic(rng) for k in range(MESSAGES)]
        out.append([(t, b"%d:%d" % (p, k)) for k, t in enumerate(topics)])
    return out


async def drain(client: MQTTClient, want: int) -> list:
    return [await client.next_message(timeout=60) for _ in range(want)]


@pytest.mark.parametrize("matcher", ["trie", "sig"])
async def test_wide_qos1_broadcast_through_a_served_broker(tmp_path, matcher):
    stored = generators.corpus(STORED, SEED, share_frac=0.1)
    plan, groups, hits = generators.find("fanout_live")(
        SEED, subscribers=SESSIONS, topics=TOPICS)
    assert groups == {} and len(plan) == SESSIONS and len(hits) == TOPICS
    path = str(tmp_path / "store.db")
    n_subs = write_store(path, stored, plan)
    ref = Reference(plan)
    sent = traffic(hits)
    # what the reference says each session is owed, publisher by publisher
    owed: dict = {cid: [] for cid in plan}
    for msgs in sent:
        for topic, payload in msgs:
            plain, shared = ref.receivers(topic)
            assert not shared
            assert set(plain) == (set(plan) if topic in hits else set())
            for cid, qos in plain.items():
                owed[cid].append((topic, payload, qos))
    broadcasts = sum(t in hits for msgs in sent for t, _p in msgs)
    assert broadcasts == PUBLISHERS * MESSAGES // 2

    conf = Config(mqtt_tcp_address="127.0.0.1:0", metrics_enabled=False,
                  matcher=matcher, mqtt_sys_topic_interval=0,
                  log_level="warn", storage_backend="sqlite",
                  storage_path=path)
    ready, stop, built = asyncio.Event(), asyncio.Event(), []
    server = asyncio.ensure_future(run_server(
        conf, Logger(out=io.StringIO(), fmt="json"), ready=ready, stop=stop,
        broker_out=built))
    clients: list[MQTTClient] = []
    try:
        await asyncio.wait_for(ready.wait(), timeout=120)
        broker = built[0]
        assert broker.topics.subscription_count == n_subs
        assert (broker.matcher is not None) == (matcher == "sig")
        port = broker.listeners.get("tcp")._server.sockets[0] \
            .getsockname()[1]
        # the sessions come back with clean_start = 0 and SUBSCRIBE nothing
        subs = {cid: MQTTClient(client_id=cid, clean_start=False)
                for cid in plan}
        clients += subs.values()
        for c in subs.values():
            await c.connect("127.0.0.1", port)
            assert c.session_present is True
        pubs = [MQTTClient(client_id=f"load-p{p}") for p in range(PUBLISHERS)]
        clients += pubs
        for c in pubs:
            await c.connect("127.0.0.1", port)

        async def publish(client: MQTTClient, msgs: list) -> None:
            for topic, payload in msgs:     # returns at the PUBACK:
                await client.publish(topic, payload, qos=1, timeout=60)

        await asyncio.wait_for(asyncio.gather(
            *(publish(c, msgs) for c, msgs in zip(pubs, sent))), 240)
        arrived = await asyncio.wait_for(asyncio.gather(
            *(drain(subs[cid], len(owed[cid])) for cid in plan)), 240)

        for cid, got in zip(plan, arrived):
            have = [(m.topic, m.payload, m.qos) for m in got]
            # the delivered set and the granted QoS, exactly; no duplicate
            assert sorted(have) == sorted(owed[cid]), cid
            assert len(set(have)) == len(have) == broadcasts
            # each publisher's messages in the order it sent them
            for p in range(PUBLISHERS):
                seqs = [int(m.payload.split(b":")[1]) for m in got
                        if m.payload.startswith(b"%d:" % p)]
                assert seqs == sorted(seqs), (cid, p)
            assert not any(m.retain for m in got)

        # every delivery was acknowledged: nothing is left in flight
        over = broker.overload
        deliveries = broadcasts * SESSIONS
        for _ in range(400):
            if over.fanout_acks >= deliveries and not broker.info.inflight:
                break
            await asyncio.sleep(0.05)
        assert over.fanout_acks == deliveries and broker.info.inflight == 0
        # ... and nothing came a second time, or to the publishers
        assert all(c.messages.empty() for c in clients)
        assert all(len(broker.clients.get(cid).inflight) == 0
                   for cid in plan)
        assert over.fanout_widest == SESSIONS
        assert over.fanout_resolved == deliveries
        if matcher == "sig":
            # no answer came from a path that failed, and the fat row as
            # the engine's own two forms give it (whichever of trie walk
            # and host probe the served batches took): 200 entries, each
            # resolved to its session at QoS 1
            sup = broker.matcher
            assert (sup.error_fallbacks, broker.matcher_degrades) == (0, 0)
            engine = sup.inner.engine
            for ask in (engine.subscribers_host_batch,
                        engine.subscribers_fixed_batch):
                (result,) = ask([hits[0]])
                pairs, shared, _matched, resolved = \
                    broker.clients.resolve(result)
                assert resolved == SESSIONS and not shared
                assert {c.id for c, _s in pairs} == set(plan)
                assert {s.qos for _c, s in pairs} == {1}
    finally:
        for c in clients:
            await c.close()
        stop.set()
        await asyncio.wait_for(server, timeout=120)


test_wide_qos1_broadcast_through_a_served_broker._async_timeout = 600
