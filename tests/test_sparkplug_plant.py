"""The ``sparkplug-plant`` deployment (perfbench/configs/sparkplug-plant.json,
Eclipse Sparkplug 3.0.0) at the rehearsal's size: its recipes are functions
of the seed with the stated counts and shapes, and on its table the sig
engine (through the MicroBatcher, device path and bypass) and the trie name
the receivers the benchmark's plain reference names."""

from __future__ import annotations

import asyncio
import json
import os
import random
import sys

import pytest

from maxmq_tpu.matching.batcher import MicroBatcher
from maxmq_tpu.matching.sig import SigEngine
from maxmq_tpu.matching.trie import TopicIndex, VersionedTopicCache
from maxmq_tpu.protocol.packets import Subscription

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
sys.path.insert(0, BENCH)

import generators  # noqa: E402  (perfbench's: recipes found by name)
from reference import Reference  # noqa: E402

SEED = 3_000_000_017        # more than 32 signed bits hold
MESSAGE_TYPES = ("NBIRTH", "NDEATH", "DBIRTH", "DDEATH", "NDATA", "DDATA",
                 "NCMD", "DCMD")


def config() -> dict:
    with open(os.path.join(BENCH, "configs", "sparkplug-plant.json")) as fh:
        conf = json.load(fh)
    with open(os.path.join(BENCH, "rehearse", "sparkplug-plant.json")) as fh:
        conf.update(json.load(fh))
    return conf


def recipes(seed: int = SEED) -> tuple:
    """(stored filters, live plan, hit topics) at the rehearsal's size."""
    conf = config()
    table, live = conf["table"], conf["live"]
    stored = generators.find(table["recipe"])(
        table["subscriptions"], seed, **table["args"])
    plan, groups, hits = generators.find(live["recipe"])(seed, **live["args"])
    assert groups == {}
    return stored, plan, hits


@pytest.fixture(scope="module")
def plant():
    """The table as the harness's store holds it (one client ``cl-<i>`` a
    stored filter, QoS ``i % 3``; the live sessions under their own ids),
    a compiled sig engine on it, and the reference over the live plan."""
    stored, plan, hits = recipes()
    index = TopicIndex()
    for i, filt in enumerate(stored):
        index.subscribe(f"cl-{i}", Subscription(filter=filt, qos=i % 3))
    for cid, subs in plan.items():
        for filt, qos in subs:
            index.subscribe(cid, Subscription(filter=filt, qos=qos))
    engine = SigEngine(index, auto_refresh=False)
    engine.refresh(force=True)
    return index, engine, Reference(plan), plan, stored, hits


def test_recipes_are_functions_of_the_seed_with_the_stated_shape():
    stored, plan, hits = recipes()
    assert (stored, plan, hits) == recipes()
    assert stored != recipes(SEED + 1)[0]
    table = config()["table"]
    assert len(stored) == table["subscriptions"] and len(stored) % 3 == 0
    # every stored edge node: its NCMD and DCMD filters and the STATE topic
    state = {f for f in stored if "/STATE/" in f}
    assert len(state) == 1 and next(iter(state)).count("/") == 2
    for ncmd, dcmd, st in zip(stored[0::3], stored[1::3], stored[2::3]):
        ns, group, kind, node, tail = ncmd.split("/")
        assert (ns, kind, tail) == ("spBv1.0", "NCMD", "#")
        assert dcmd == f"spBv1.0/{group}/DCMD/{node}/#" and st in state
    assert len(set(stored[0::3])) == len(stored) // 3
    # the live population: 256 edge nodes with the same three filters,
    # two hosts on the whole namespace, ten area applications
    edges = {c: s for c, s in plan.items() if c.startswith("sp-edge-")}
    assert len(plan) == 268 and len(edges) == 256
    assert sum(len(s) for s in plan.values()) == 821
    assert all(q == 1 for s in plan.values() for _f, q in s)
    live_filters = {f for s in edges.values() for f, _q in s} - state
    assert len(live_filters) == 512
    assert not live_filters & set(stored)       # live and stored apart
    assert plan["sp-host-primary"] == [("spBv1.0/#", 1),
                                       (next(iter(state)), 1)]
    assert plan["sp-host-historian"] == [("spBv1.0/#", 1)]
    areas = [f for k in range(10) for f, _q in plan[f"sp-host-area{k}"]]
    assert len(areas) == len(set(areas)) == 50
    assert all(f.count("/") == 2 and f.endswith("/#") for f in areas)
    assert len(hits) == 256 * 11
    assert sum("/NCMD/" in t for t in hits) == 256


def test_full_size_counts_are_the_configurations():
    """29,232 stored subscriptions of 9,744 nodes; asking for more than
    the plant stores is an error, not a shorter table."""
    with open(os.path.join(BENCH, "configs", "sparkplug-plant.json")) as fh:
        table = json.load(fh)["table"]
    make = generators.find(table["recipe"])
    full = make(table["subscriptions"], SEED, **table["args"])
    assert len(full) == 29_232 == 3 * (50 * 200 - 256)
    assert len(set(full)) == 2 * 9_744 + 1
    with pytest.raises(ValueError):
        make(table["subscriptions"] + 3, SEED, **table["args"])


def test_topic_mix_is_the_traffic_files():
    with open(os.path.join(BENCH, "traffic", "sparkplug-steady.json")) as fh:
        traffic = json.load(fh)
    assert (traffic["loop"], traffic["qos1_share"],
            traffic["payload_bytes"]) == ("open", 0, [48, 400])
    _stored, _plan, hits = recipes()
    draw = generators.topic_source(traffic, SEED, hits)
    rng = random.Random(5)
    topics = [draw(rng) for _ in range(20_000)]
    share = {k: sum(f"/{k}/" in t for t in topics) / len(topics)
             for k in ("DDATA", "NDATA", "NCMD", "DCMD")}
    assert abs(share["DDATA"] - 0.88) < 0.01
    assert abs(share["NDATA"] - 0.10) < 0.01
    assert abs(share["NCMD"] - 0.01) < 0.004
    assert abs(share["DCMD"] - 0.01) < 0.004
    # a closed set with no hot head: 20,000 draws of 110,000 topics
    assert len(set(topics)) > 17_000
    assert {t for t in topics if "CMD/" in t} <= set(hits)
    assert all(len(t.split("/")) == (5 if "/D" in t else 4) for t in topics)


def sample_topics(stored: list, plan: dict, hits: list) -> list[str]:
    """Seeded topics of every message type for live, stored and unknown
    nodes, the STATE topic, and what no Sparkplug filter may reach."""
    rng = random.Random(SEED)
    nodes = [f.split("/")[1::2] for f in stored[0::3]]
    nodes += [f.split("/")[1::2] for c, s in plan.items()
              if c.startswith("sp-edge-") for f, _q in s[:1]]
    nodes += [("press-0000", "line-00000"), (nodes[0][0], "rtu-fffff")]
    topics = []
    for _ in range(2_200):
        group, node = rng.choice(nodes)
        kind = rng.choice(MESSAGE_TYPES)
        topic = f"spBv1.0/{group}/{kind}/{node}"
        if kind.startswith("D"):
            topic += f"/d{rng.randrange(12):02d}"
        topics.append(topic)
    state = stored[2]
    return topics + rng.sample(hits, 300) + [
        state, state + "/x", "spBv1.0/STATE/other", "spBv1.0",
        "$SYS/broker/uptime", "spAv1.0/x/DDATA/y/z"]


def expected(ref: Reference, topic: str) -> dict:
    plain, shared = ref.receivers(topic)
    assert shared == {}
    return plain


def plain_subscriptions(result) -> dict:
    """A match result's plain entries, whichever type the path gave."""
    if hasattr(result, "to_set"):       # DeliveryIntents (ADR 007)
        result = result.to_set()
    return result.subscriptions


def live_receivers(result, plan: dict) -> dict:
    """client -> granted QoS, for the sessions that are live: the stored
    ``cl-<i>`` have no session and the fan-out drops them."""
    return {cid: s.qos for cid, s in plain_subscriptions(result).items()
            if cid in plan}


def test_trie_names_the_references_receivers(plant):
    index, _engine, ref, plan, stored, hits = plant
    for topic in sample_topics(stored, plan, hits):
        got = live_receivers(index.subscribers(topic), plan)
        assert got == expected(ref, topic), topic


@pytest.mark.parametrize("path", ["device", "bypass"])
async def test_sig_engine_through_the_batcher_names_the_references_receivers(
        plant, path):
    _index, engine, ref, plan, stored, hits = plant
    topics = sample_topics(stored, plan, hits)
    batcher = MicroBatcher(engine, window_us=0,
                           cpu_bypass=path == "bypass")
    if path == "bypass":
        batcher._device_rtt = 1.0       # every batch undercuts it
        batcher._trie_cost = 1.0        # ... by the host probe, not the trie
    try:
        results = await asyncio.gather(
            *(batcher.enqueue(t) for t in topics))
    finally:
        await batcher.close()
    for topic, result in zip(topics, results):
        assert live_receivers(result, plan) == expected(ref, topic), topic
    if path == "bypass":
        assert batcher.bypasses == batcher.batched_topics > 0
        assert engine.host_matches > 0
    else:
        assert batcher.bypasses == 0
    # the STATE topic's fat row: every edge node, live and stored
    state = plain_subscriptions(engine.subscribers(stored[2]))
    assert len(state) == len(stored) // 3 + 256 + 2


def test_topic_cache_counts_its_evictions():
    cache = VersionedTopicCache(maxsize=64)
    for k in range(3 * cache.maxsize):
        cache.put(f"spBv1.0/g/DDATA/n/d{k}", 1, k)
    assert len(cache) == cache.maxsize
    assert cache.evictions == 2 * cache.maxsize
    cache.put("spBv1.0/g/DDATA/n/d191", 2, "again")     # held: no eviction
    assert cache.evictions == 2 * cache.maxsize
    assert cache.get("spBv1.0/g/DDATA/n/d0", 1) is None


async def test_batcher_and_metrics_export_the_topic_caches_counters(plant):
    from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities
    from maxmq_tpu.metrics import Registry, register_broker_metrics
    _index, engine, _ref, _plan, _stored, hits = plant
    batcher = MicroBatcher(engine, window_us=0)
    batcher._cache = VersionedTopicCache(maxsize=16)
    try:
        await asyncio.gather(*(batcher.enqueue(t) for t in hits[:48]))
    finally:
        await batcher.close()
    assert (batcher.topic_cache_size, batcher.topic_cache_evictions) == \
        (16, 32)
    broker = Broker(BrokerOptions(
        capabilities=Capabilities(sys_topic_interval=0)))
    broker.attach_matcher(batcher)
    reg = Registry()
    register_broker_metrics(reg, broker)
    text = reg.expose()
    assert "maxmq_matcher_topic_cache_evictions_total 32" in text
    assert "maxmq_matcher_topic_cache_size 16" in text
    assert "maxmq_broker_match_cache_evictions_total 0" in text
    assert "maxmq_broker_match_cache_size 0" in text
