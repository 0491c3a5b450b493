"""Cluster-mode matcher: shard_map parity on the 8-device CPU mesh."""

import random

import pytest

import jax

from maxmq_tpu.matching.trie import TopicIndex
from maxmq_tpu.parallel.sharded import ShardedSigEngine, make_mesh
from maxmq_tpu.protocol.packets import Subscription

from matching_helpers import normalize

ALPHABET = ["alpha", "beta", "gamma", "delta", "eps", "zeta"]


def random_corpus(n_filters, n_topics, seed):
    rng = random.Random(seed)

    def filt():
        depth = rng.randint(1, 6)
        levels = [rng.choice(ALPHABET) for _ in range(depth)]
        r = rng.random()
        if r < 0.3:
            levels[rng.randrange(depth)] = "+"
        elif r < 0.45:
            levels = levels[: rng.randint(1, depth)] + ["#"]
        f = "/".join(levels)
        if rng.random() < 0.15:
            f = f"$share/grp{rng.randint(0, 2)}/{f}"
        return f

    filters = [filt() for _ in range(n_filters)]
    topics = ["/".join(rng.choice(ALPHABET)
                       for _ in range(rng.randint(1, 6)))
              for _ in range(n_topics)]
    topics += ["$SYS/broker/load", "a//b", "/leading"]
    return filters, topics


def build_index(filters):
    index = TopicIndex()
    for i, f in enumerate(filters):
        index.subscribe(f"c{i}", Subscription(filter=f, qos=i % 3))
    return index


def assert_same(got, want, topic):
    assert set(got.subscriptions) == set(want.subscriptions), topic
    for cid, sub in want.subscriptions.items():
        assert got.subscriptions[cid].qos == sub.qos, (topic, cid)
    assert set(got.shared) == set(want.shared), topic
    for key, members in want.shared.items():
        assert set(got.shared[key]) == set(members), (topic, key)


def test_make_mesh_default_shape():
    mesh = make_mesh()
    assert mesh.devices.size == len(jax.devices())
    assert set(mesh.axis_names) == {"data", "subs"}


def test_graft_entry_single_chip():
    import numpy as np

    import __graft_entry__ as ge

    fn, example_args = ge.entry()
    counts, stream = fn(*example_args)      # stream wire format
    batch = example_args[0].shape[0]
    counts = np.asarray(counts)
    assert counts.shape == (batch,)
    assert (counts != 255).all()            # 255 = overflow sentinel;
    total = int(counts.sum())               # this corpus never overflows
    assert 0 < total <= stream.shape[0]


def test_graft_entry_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


# ----------------------------------------------------- sharded sig engine

@pytest.mark.parametrize("shape", [(1, 8), (2, 4), (4, 2)])
def test_sharded_sig_parity_vs_trie(shape):
    filters, topics = random_corpus(300, 64, seed=shape[0] * 17 + shape[1])
    index = build_index(filters)
    mesh = make_mesh(shape=shape)
    engine = ShardedSigEngine(index, mesh=mesh)
    got = engine.subscribers_batch(topics)
    for topic, g in zip(topics, got):
        assert_same(g, index.subscribers(topic), topic)


def test_sharded_sig_refresh_and_fallback():
    filters, topics = random_corpus(100, 16, seed=9)
    index = build_index(filters)
    engine = ShardedSigEngine(index, mesh=make_mesh(shape=(2, 4)))
    index.subscribe("late", Subscription(filter="alpha/#", qos=1))
    got = engine.subscribers("alpha/beta")
    assert "late" in got.subscriptions
    # deep topic -> CPU fallback, still exact
    deep = "/".join(["alpha"] * 80)
    index.subscribe("deepc", Subscription(filter="/".join(["alpha"] * 80)))
    got = engine.subscribers(deep)
    assert_same(got, index.subscribers(deep), deep)


def test_sharded_sig_padding_words_cannot_fire():
    """Padding word slots must point at the all-zero-coefficient padding
    group (signature deterministically 0, never the 0xFFFFFFFF poison
    plane) — a real group's signature can adversarially equal the poison
    and emit row ids past the shard's row tables."""
    import numpy as np

    filters, _topics = random_corpus(60, 0, seed=3)
    index = build_index(filters)
    engine = ShardedSigEngine(index, mesh=make_mesh(shape=(1, 8)))
    _v, shards, dev, fn, _d, _ue, _dp, _ck = engine._state
    assert fn is not None
    topo = np.asarray(dev[0])           # [sp, G, D] coefficients
    dc = np.asarray(dev[1])             # [sp, G] depth coefficients
    grp = np.asarray(dev[6])            # [sp, W] word -> group
    for s, t in enumerate(shards):
        w = int(t.group_words.sum())
        pad_groups = np.unique(grp[s, w:])
        assert topo[s, pad_groups].sum() == 0, s
        assert dc[s, pad_groups].sum() == 0, s


def test_sharded_sig_scale_100k_and_reshard():
    """Scale-up cluster parity (VERDICT r1 #7): >=100K filters with
    mixed $share/'#'/deep shapes over 8 shards must match the trie
    exactly — the cross-shard invariants (shared intern pool, union
    exact groups, shard-0 tokenization serving all shards) only break
    at scale. Then simulate losing half the mesh: reshard to 4 devices
    and assert exact parity again (elastic recovery by recompile)."""
    rng = random.Random(77)
    alphabet = [f"{c}{i}" for c in "abcdefgh" for i in range(12)]
    filters = []
    for _ in range(100_000):
        depth = rng.randint(1, 8)
        levels = [rng.choice(alphabet) for _ in range(depth)]
        r = rng.random()
        if r < 0.3:
            levels[rng.randrange(depth)] = "+"
        elif r < 0.45:
            levels = levels[: rng.randint(1, depth)] + ["#"]
        f = "/".join(levels)
        if rng.random() < 0.1:
            f = f"$share/g{rng.randint(0, 4)}/{f}"
        filters.append(f)
    index = build_index(filters)
    topics = ["/".join(rng.choice(alphabet)
                       for _ in range(rng.randint(1, 8)))
              for _ in range(256)]
    topics += ["$SYS/broker/load", "a0//b0", "/a0"]

    engine = ShardedSigEngine(index, mesh=make_mesh(shape=(1, 8)))
    got = engine.subscribers_batch(topics)
    n_matched = 0
    for topic, g in zip(topics, got):
        want = index.subscribers(topic)
        assert_same(g, want, topic)
        n_matched += len(want.subscriptions) + len(want.shared)
    assert n_matched > 500, "corpus too sparse to be a meaningful test"

    # half the devices "fail": recompile over a (1, 4) mesh
    engine.reshard(make_mesh(shape=(1, 4)))
    assert engine.sp == 4
    got = engine.subscribers_batch(topics[:64])
    for topic, g in zip(topics[:64], got):
        assert_same(g, index.subscribers(topic), topic)


def test_sharded_sig_multislice_mesh_parity():
    """DCN/multi-slice story: subscriptions partition over
    ('slice', 'subs') jointly; the match program never communicates
    across 'slice', so only host result gathers cross the (slow)
    inter-slice fabric. Virtual 2-slice x (data 1|2 x subs 2) meshes
    must match the trie exactly."""
    from maxmq_tpu.parallel.sharded import make_multislice_mesh

    filters, topics = random_corpus(400, 48, seed=21)
    index = build_index(filters)
    for shape in [(1, 2), (2, 2)]:
        mesh = make_multislice_mesh(n_slices=2, shape=shape)
        assert mesh.axis_names == ("slice", "data", "subs")
        engine = ShardedSigEngine(index, mesh=mesh)
        assert engine.sp == 2 * shape[1]
        got = engine.subscribers_batch(topics)
        for topic, g in zip(topics, got):
            assert_same(g, index.subscribers(topic), topic)

    # elastic: drop to a single-slice 2-axis mesh and back
    engine.reshard(make_mesh(shape=(1, 4)))
    got = engine.subscribers_batch(topics[:16])
    for topic, g in zip(topics[:16], got):
        assert_same(g, index.subscribers(topic), topic)


def test_sharded_sig_uneven_and_empty_shards():
    # fewer filters than shards: some shards compile empty
    index = build_index(["alpha/beta", "alpha/+", "gamma/#"])
    engine = ShardedSigEngine(index, mesh=make_mesh(shape=(1, 8)))
    for topic in ["alpha/beta", "gamma/x/y", "delta", "alpha"]:
        assert_same(engine.subscribers(topic), index.subscribers(topic),
                    topic)


async def test_cluster_broker_qos12_offline_redelivery():
    """BASELINE config 5 end-to-end (VERDICT r03 #6): a real broker with
    the ShardedSigEngine attached drives QoS1 and QoS2 flows — live
    delivery, exactly-once dedup, and persistent-session offline
    redelivery — with every match answered by the sharded matcher on
    the 8-device CPU mesh."""
    import asyncio

    from test_broker_system import connect, running_broker

    from maxmq_tpu.matching.batcher import MicroBatcher
    from maxmq_tpu.mqtt_client import MQTTClient

    async with running_broker() as broker:
        eng = ShardedSigEngine(broker.topics, mesh=make_mesh())
        mb = MicroBatcher(eng, window_us=0, cpu_bypass=False)
        broker.attach_matcher(mb)
        s = await connect(broker, "cs-sub", clean_start=False)
        await s.subscribe(("cs/q/#", 1), ("cs/e/t", 2))
        p = await connect(broker, "cs-pub")

        # QoS1 live delivery through the sharded matcher
        await p.publish("cs/q/a", b"live", qos=1)
        m = await s.next_message(timeout=60)
        assert (m.topic, m.payload, m.qos) == ("cs/q/a", b"live", 1)

        # QoS2 exactly-once through the sharded matcher
        for i in range(3):
            await p.publish("cs/e/t", f"m{i}".encode(), qos=2)
        got = [await s.next_message(timeout=60) for _ in range(3)]
        assert [g.payload for g in got] == [b"m0", b"m1", b"m2"]
        assert all(g.qos == 2 for g in got)

        # the sharded engine answered the matches (not a trie fallback).
        # Only DISTINCT topics are guaranteed to reach the engine — the
        # batcher's version-keyed cache may serve repeats (that's its
        # job), so the floor is 2 (cs/q/a, cs/e/t), not one per publish.
        assert eng.matches >= 2
        fallback_frac = eng.fallbacks / max(eng.matches, 1)
        assert fallback_frac < 0.5, (eng.fallbacks, eng.matches)

        # persistent-session offline QoS1 redelivery: the sharded match
        # must still name the disconnected session's client
        await s.close()                    # network drop, not DISCONNECT
        await asyncio.sleep(0.1)
        await p.publish("cs/q/offline", b"queued", qos=1)
        s2 = MQTTClient(client_id="cs-sub", clean_start=False)
        await s2.connect("127.0.0.1", broker.test_port)
        assert s2.connack.session_present is True
        m = await s2.next_message(timeout=60)
        assert (m.payload, m.qos) == (b"queued", 1)
        await s2.disconnect()
        await p.disconnect()
        await mb.close()


def test_sharded_chain_in_chain_parity():
    """Cluster chain composition: per-shard results that are themselves
    CHAINED intents (fat '#' bucket split across client-hash shards)
    iterate correctly inside the cluster-level ChainedIntents — no
    duplicate clients, exact trie parity, n/len/to_set agree."""
    from maxmq_tpu.native import decode_module
    mod = decode_module()
    if mod is None or not hasattr(mod, "_set_chain_params"):
        pytest.skip("maxmq_decode extension unavailable")
    from maxmq_tpu.parallel.sharded import ChainedIntents

    idx = TopicIndex()
    for i in range(200):
        idx.subscribe(f"fat{i}", Subscription(filter="cc/dev/#", qos=1))
    idx.subscribe("fat3", Subscription(filter="cc/dev/a/b", qos=2,
                                       identifier=5))
    idx.subscribe("solo", Subscription(filter="cc/dev/+/b", qos=1))
    idx.subscribe("sh1", Subscription(filter="$share/g/cc/dev/#", qos=1))
    # client-hash sharding splits the 200 fat clients ~25 per shard —
    # drop the chain threshold so every shard's fat row anchors a chain
    from maxmq_tpu.native import chain_params_in_effect
    saved = chain_params_in_effect(mod)
    mod._set_chain_params(8, 4, 1)
    try:
        eng = ShardedSigEngine(idx, mesh=make_mesh())
        eng.emit_intents = True
        topics = ["cc/dev/a/b", "cc/dev/x/b", "cc/dev/z", "no/match"]
        got = eng.subscribers_batch(topics)
        saw_nested = 0
        for topic, r in zip(topics, got):
            want = idx.subscribers(topic)
            if not isinstance(r, ChainedIntents):
                assert normalize(getattr(r, "to_set", lambda: r)()) \
                    == normalize(want), topic
                continue
            saw_nested += sum(
                1 for p in r.parts if getattr(p, "chained", False))
            by_iter = {}
            for cid, sub in r:
                assert cid not in by_iter, (topic, cid)
                by_iter[cid] = sub
            assert len(by_iter) == r.n, topic
            assert set(by_iter) == set(want.subscriptions), topic
            for cid, sub in by_iter.items():
                w = want.subscriptions[cid]
                assert (sub.qos, dict(sub.identifiers)) == \
                    (w.qos, dict(w.identifiers)), (topic, cid)
            assert normalize(r.to_set()) == normalize(want), topic
        assert saw_nested, "no per-shard chained intents engaged"
    finally:
        mod._set_chain_params(*saved)


@pytest.mark.parametrize("seed", [21, 22])
def test_sharded_intents_parity(seed):
    """Cluster-mode ADR 007: chained per-shard DeliveryIntents must
    match the CPU trie exactly (client-hash sharding makes the chain
    merge-free), including $share groups spanning shards and the
    to_set()/resolve surface."""
    from maxmq_tpu.native import decode_module
    if decode_module() is None:
        pytest.skip("maxmq_decode extension unavailable")
    from maxmq_tpu.parallel.sharded import ChainedIntents

    filters, topics = random_corpus(250, 120, seed)
    idx = TopicIndex()
    from maxmq_tpu.matching.topics import valid_filter
    rng = random.Random(seed)
    for i, f in enumerate(filters):
        if not valid_filter(f):
            continue
        idx.subscribe(f"cl{i % 60}",
                      Subscription(filter=f, qos=rng.randint(0, 2),
                                   identifier=rng.randint(0, 3)))
    eng = ShardedSigEngine(idx, mesh=make_mesh())
    eng.emit_intents = True
    got = eng.subscribers_batch(topics)
    saw_chained = 0
    for topic, r in zip(topics, got):
        want = idx.subscribers(topic)
        if isinstance(r, ChainedIntents):
            saw_chained += 1
            by_iter = {cid: sub for cid, sub in r}
            assert len(by_iter) == r.n, f"client chained twice: {topic}"
            assert set(by_iter) == set(want.subscriptions), topic
            for cid, sub in by_iter.items():
                assert r.resolve({cid: cid})[0] == [(cid, sub)]
            s = r.to_set()
            assert normalize(s) == normalize(want), topic
        else:
            to_set = getattr(r, "to_set", None)
            s = to_set() if to_set is not None else r
            assert normalize(s) == normalize(want), topic
    assert saw_chained, "chained intents path never engaged"


@pytest.mark.parametrize("registry", ["empty", "all", "third"])
def test_sharded_intents_resolve(registry):
    """ChainedIntents.resolve (ADR 007): the per-shard passes chained,
    with a surviving $share key keeping the member map merged across
    shards (the rotation indexes the group's whole candidate set)."""
    from maxmq_tpu.native import decode_module
    if decode_module() is None:
        pytest.skip("maxmq_decode extension unavailable")
    from maxmq_tpu.parallel.sharded import ChainedIntents

    idx = TopicIndex()
    for i in range(60):
        idx.subscribe(f"cl{i}", Subscription(filter="rs/#", qos=i % 3))
    for i in range(0, 60, 4):
        idx.subscribe(f"cl{i}", Subscription(filter="$share/g/rs/+",
                                             qos=1))
        idx.subscribe(f"sh{i}", Subscription(filter="$share/h/rs/a",
                                             qos=1))
    eng = ShardedSigEngine(idx, mesh=make_mesh())
    eng.emit_intents = True
    results = [r for r in eng.subscribers_batch(["rs/a", "rs/b", "no/x"])
               if isinstance(r, ChainedIntents)]
    assert results, "chained intents path never engaged"
    rng = random.Random(registry)
    cids = [f"cl{i}" for i in range(60)] + [f"sh{i}"
                                            for i in range(0, 60, 4)]
    keep = {"empty": [], "all": cids,
            "third": rng.sample(cids, len(cids) // 3)}[registry]
    reg = {cid: object() for cid in keep}
    for r in results:
        pairs, shared, matched, resolved = r.resolve(reg)
        assert pairs == [(reg[cid], sub) for cid, sub in r if cid in reg]
        want = {k: m for k, m in r.shared.items()
                if any(cid in reg for cid in m)}
        assert shared == want and list(shared) == list(want)
        assert matched == len(r)
        assert resolved == len(pairs) + sum(
            cid in reg for m in r.shared.values() for cid in m)
    spans = [k for r in results for k, m in r.shared.items()
             if not any(len(m) == len(p.shared.get(k, ()))
                        for p in r.parts)]
    assert spans, "no $share group spanned shards"


async def test_sharded_intents_broker_delivery():
    """The broker consumes ChainedIntents end-to-end (QoS1 + $share)."""
    from test_broker_system import connect, running_broker

    from maxmq_tpu.matching.batcher import MicroBatcher

    async with running_broker() as broker:
        eng = ShardedSigEngine(broker.topics, mesh=make_mesh())
        eng.emit_intents = True
        mb = MicroBatcher(eng, window_us=0, cpu_bypass=False)
        broker.attach_matcher(mb)
        s = await connect(broker, "ci-sub", version=5)
        await s.subscribe(("ci/+/x", 1))
        g1 = await connect(broker, "ci-g1")
        await g1.subscribe(("$share/g/ci/sh", 0))
        p = await connect(broker, "ci-pub")
        await p.publish("ci/a/x", b"one", qos=1)
        m = await s.next_message(timeout=60)
        assert (m.topic, m.payload, m.qos) == ("ci/a/x", b"one", 1)
        await p.publish("ci/sh", b"sh")
        m = await g1.next_message(timeout=60)
        assert m.payload == b"sh"
        for c in (s, g1, p):
            await c.disconnect()
        await mb.close()




def test_heavy_client_falls_back_to_round_robin(monkeypatch):
    """One client whose wildcard shapes overflow a client-hash bucket's
    MAX_GROUPS must not disable device matching: refresh re-partitions
    round-robin (spreading the shapes) and turns chaining off, with
    exact results either way."""
    import maxmq_tpu.matching.sig as sigmod
    from maxmq_tpu.parallel.sharded import ChainedIntents

    monkeypatch.setattr(sigmod, "MAX_GROUPS", 4)
    idx = TopicIndex()
    # a bridge client with 8 distinct '#'-shapes (device groups; depth
    # varies — trailing-'+' shapes would be host-probed, not grouped)
    for d in range(2, 10):
        idx.subscribe("bridge", Subscription(
            filter="/".join(["alpha"] * d) + "/#", qos=1))
    idx.subscribe("plain", Subscription(filter="alpha/beta", qos=0))
    eng = ShardedSigEngine(idx, mesh=make_mesh(shape=(1, 8)))
    eng.emit_intents = True
    assert eng._state[3] is not None, "device path must stay alive"
    assert eng._state[7] is False, "chaining must be off under round-robin"
    topics = ["alpha/beta", "alpha/alpha/x", "alpha/alpha/alpha/y"]
    got = eng.subscribers_batch(topics)
    for t, r in zip(topics, got):
        assert not isinstance(r, ChainedIntents)
        assert normalize(r) == normalize(idx.subscribers(t)), t


def test_client_hash_empty_buckets_ok():
    """Client-hash partitioning with fewer clients than shards leaves
    empty buckets — matching and chaining must work regardless."""
    from maxmq_tpu.native import decode_module
    if decode_module() is None:
        pytest.skip("maxmq_decode extension unavailable")

    idx = TopicIndex()
    idx.subscribe("only-a", Subscription(filter="eb/+/t", qos=1))
    idx.subscribe("only-b", Subscription(filter="eb/#", qos=0))
    eng = ShardedSigEngine(idx, mesh=make_mesh(shape=(1, 8)))
    eng.emit_intents = True
    got = eng.subscribers_batch(["eb/x/t", "eb/y", "zz"])
    s0 = got[0].to_set() if hasattr(got[0], "to_set") else got[0]
    assert set(s0.subscriptions) == {"only-a", "only-b"}
    s1 = got[1].to_set() if hasattr(got[1], "to_set") else got[1]
    assert set(s1.subscriptions) == {"only-b"}
    assert len(got[2]) == 0


@pytest.mark.parametrize("seed", [21, 22])
def test_sharded_host_batch_parity(seed):
    """Cluster-mode device-free path (subscribers_host_batch: per-shard
    exact/'+'/'#' host probes + chained native decode, no mesh
    dispatch) matches the CPU trie exactly in both result forms."""

    filters, topics = random_corpus(250, 120, seed)
    idx = TopicIndex()
    from maxmq_tpu.matching.topics import valid_filter
    rng = random.Random(seed)
    for i, f in enumerate(filters):
        if not valid_filter(f):
            continue
        idx.subscribe(f"cl{i % 60}",
                      Subscription(filter=f, qos=rng.randint(0, 2),
                                   identifier=rng.randint(0, 3)))
    eng = ShardedSigEngine(idx, mesh=make_mesh())
    for emit in (False, True):
        eng.emit_intents = emit
        got = eng.subscribers_host_batch(topics)
        for topic, r in zip(topics, got):
            want = idx.subscribers(topic)
            to_set = getattr(r, "to_set", None)
            s = to_set() if to_set is not None else r
            assert normalize(s) == normalize(want), (topic, emit)
    assert eng.host_matches == 2 * len(topics)


def test_sharded_host_batch_overflow_topic_falls_back():
    """Regression: prepare_batch_sig reports too-deep topics as
    lengths == -1 (not >= 127) — the host path must still serve them
    from the trie, exactly like the device path's 0xF marker."""
    idx = TopicIndex()
    idx.subscribe("deepwatch", Subscription(filter="#", qos=1))
    idx.subscribe("plain", Subscription(filter="alpha/beta", qos=0))
    eng = ShardedSigEngine(idx, mesh=make_mesh())
    deep = "/".join(["alpha"] * 80)          # > DEPTH_CAP
    for emit in (False, True):
        eng.emit_intents = emit
        before = eng.host_matches
        got = eng.subscribers_host_batch([deep, "alpha/beta"])
        to_set = getattr(got[0], "to_set", None)
        s0 = to_set() if to_set is not None else got[0]
        assert "deepwatch" in s0.subscriptions, "overflow topic lost"
        # the overflow topic was trie-served, not a host match
        assert eng.host_matches == before + 1
