"""Threaded churn stress — the repo's analogue of the reference's
always-on race detector (`-race` on every unit invocation,
/root/reference/Makefile:105).

Concurrency model under test = production's: ONE mutator thread (the
broker's event loop serializes subscribes) churning the index while N
executor threads run subscribers_batch concurrently (the MicroBatcher's
pipelined collect path, pipeline_depth > 1). Exercised surfaces: the
SigEngine refresh()/overlay swap, the journal, the native decode caches
(row-set, fragment, intents — including the single-builder scratch's
concurrent-entry fallback), and the sharded engine's shard_map path.

Parity assertion: batches that ran inside a quiescent version window
(no mutation between dispatch and the trie re-check) must match the
CPU trie exactly; batches that overlapped a mutation only need to be
well-formed (staleness there is bounded by the overlay contract, which
test_sig_parity's randomized_churn_parity pins sequentially).
"""

import dataclasses
import random
import threading
import time

import pytest

from maxmq_tpu.matching import TopicIndex
from maxmq_tpu.matching.sig import SigEngine
from maxmq_tpu.protocol import Subscription

from matching_helpers import normalize

ALPHABET = [f"s{i}" for i in range(10)]


def _rand_filter(rng) -> str:
    depth = rng.randint(1, 5)
    levels = [rng.choice(ALPHABET) for _ in range(depth)]
    r = rng.random()
    if r < 0.25:
        levels[rng.randrange(depth)] = "+"
    elif r < 0.35:
        levels = levels[: rng.randint(1, depth)] + ["#"]
    f = "/".join(levels)
    if rng.random() < 0.1:
        f = f"$share/g{rng.randint(0, 2)}/{f}"
    return f


def _rand_topic(rng) -> str:
    return "/".join(rng.choice(ALPHABET)
                    for _ in range(rng.randint(1, 5)))


def _seed(idx, n=1500, clients=200, seed=3) -> None:
    rng = random.Random(seed)
    for i in range(n):
        idx.subscribe(f"c{i % clients}",
                      Subscription(filter=_rand_filter(rng),
                                   qos=rng.randint(0, 2),
                                   identifier=rng.randint(0, 3)))


def _as_set(r):
    to_set = getattr(r, "to_set", None)
    return to_set() if to_set is not None else r


_SUB_FIELDS = frozenset(
    f.name for f in dataclasses.fields(Subscription))


def _assert_no_grafted_referents(engine, topics):
    """Sampled enforcement of the no-cycles contract (ADR 009): intents
    results are untracked by the GC, so a consumer that grafts a
    reference onto a shared Subscription record would create a silent
    permanent leak instead of collectable garbage. Sample the cached
    records a real batch returns and assert they hold only their
    declared dataclass fields, with ``identifiers`` still a pure
    str->int map — any foreign attribute or grafted object fails
    loudly here instead of leaking silently in production."""
    for res in engine.subscribers_fixed_batch(topics):
        subs = _as_set(res).subscriptions
        for rec in subs.values():
            extra = set(vars(rec)) - _SUB_FIELDS
            assert not extra, f"grafted attributes on Subscription: {extra}"
            for k, v in rec.identifiers.items():
                assert type(k) is str and type(v) is int, (
                    f"identifiers polluted: {k!r} -> {type(v)}")


def _storm(engine, idx, duration_s: float, n_readers: int,
           batch_fn_name: str = "subscribers_fixed_batch"):
    """One mutator + n_readers matcher threads for duration_s.
    Returns (quiescent_batches_checked, total_batches, errors)."""
    stop = threading.Event()
    errors: list = []
    checked = [0]
    total = [0]

    def matcher(tid: int):
        rng = random.Random(1000 + tid)
        batch_fn = getattr(engine, batch_fn_name,
                           engine.subscribers_batch)
        try:
            while not stop.is_set():
                topics = [_rand_topic(rng) for _ in range(32)]
                v0 = idx.sub_version
                got = batch_fn(topics)
                total[0] += 1
                assert len(got) == len(topics)
                if idx.sub_version != v0:
                    continue               # overlapped a mutation
                want = [idx.subscribers(t) for t in topics]
                if idx.sub_version != v0:
                    continue               # mutated under the re-check
                for t, g, w in zip(topics, got, want):
                    assert normalize(_as_set(g)) == normalize(w), t
                checked[0] += 1
        except Exception as exc:
            errors.append((f"matcher-{tid}", repr(exc)))

    churn_stop = threading.Event()

    def churner_bounded():
        rng = random.Random(99)
        i = 0
        try:
            while not churn_stop.is_set():
                cid = f"churn-{rng.randint(0, 40)}"
                f = _rand_filter(rng)
                idx.subscribe(cid, Subscription(filter=f,
                                                qos=rng.randint(0, 2)))
                if rng.random() < 0.6:
                    idx.unsubscribe(cid, f)
                i += 1
                if i % 25 == 0:
                    time.sleep(0)          # let readers interleave
        except Exception as exc:           # pragma: no cover
            errors.append(("churner", repr(exc)))

    threads = [threading.Thread(target=churner_bounded, daemon=True)]
    threads += [threading.Thread(target=matcher, args=(i,), daemon=True)
                for i in range(n_readers)]
    for t in threads:
        t.start()
    # phase 1: churn + match concurrently; phase 2: index quiet while
    # readers keep matching — guarantees quiescent parity checks even
    # when phase-1 windows never settle
    time.sleep(duration_s * 0.6)
    churn_stop.set()
    deadline = time.time() + max(duration_s, 30)
    while checked[0] < 2 and time.time() < deadline and not errors:
        time.sleep(0.2)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    return checked[0], total[0], errors


def test_threaded_churn_sig_intents():
    """Sig engine, intents decode, 3 concurrent readers vs churn: the
    native caches and the intents scratch must never produce a wrong or
    malformed batch, and quiescent batches must be exactly right."""
    idx = TopicIndex()
    _seed(idx)
    eng = SigEngine(idx)
    eng.emit_intents = True
    eng.route_small = False   # storm the device decode, not the trie
    checked, total, errors = _storm(eng, idx, duration_s=6, n_readers=3)
    assert not errors, errors
    assert total > 5, "storm produced too few batches to mean anything"
    assert checked > 0, "no quiescent window ever checked parity"
    rng = random.Random(7)
    _assert_no_grafted_referents(eng, [_rand_topic(rng) for _ in range(64)])


def test_threaded_churn_sig_sets():
    """Same storm over the merged-set decode (row-set + fragment
    caches)."""
    idx = TopicIndex()
    _seed(idx)
    eng = SigEngine(idx)
    eng.route_small = False
    checked, total, errors = _storm(eng, idx, duration_s=5, n_readers=2)
    assert not errors, errors
    assert total > 5 and checked > 0


def test_threaded_churn_sig_chained():
    """The chained-intents path under the same storm: a fat '#' bucket
    forces chains (threshold lowered), so concurrent readers exercise
    the row_base publish-once race, the per-row slot maps, and chained
    iteration while the mutator rotates tables."""
    from maxmq_tpu.native import decode_module
    mod = decode_module()
    if mod is None or not hasattr(mod, "_set_chain_params"):
        pytest.skip("maxmq_decode extension unavailable")
    idx = TopicIndex()
    _seed(idx, n=800, clients=120)
    for i in range(120):
        idx.subscribe(f"fat{i}", Subscription(filter="s0/#", qos=1))
    from maxmq_tpu.native import chain_params_in_effect
    saved = chain_params_in_effect(mod)
    mod._set_chain_params(16, 4, 1)
    try:
        eng = SigEngine(idx)
        eng.emit_intents = True
        eng.route_small = False
        checked, total, errors = _storm(eng, idx, duration_s=6,
                                        n_readers=3)
        assert not errors, errors
        assert total > 5 and checked > 0
        # the chained path must actually engage: a thin filter overlapping
        # the fat bucket guarantees a 2-row set, and a forced refresh
        # settles any open overlay window (intents only emit with the
        # overlay closed)
        idx.subscribe("probe-thin", Subscription(filter="s0/a/b", qos=0))
        eng.refresh(force=True)
        got = eng.subscribers_fixed_batch(["s0/a/b"])
        assert getattr(got[0], "chained", False), repr(got[0])
        rng = random.Random(11)
        _assert_no_grafted_referents(
            eng, ["s0/a/b"] + [_rand_topic(rng) for _ in range(32)])
    finally:
        mod._set_chain_params(*saved)


def test_threaded_churn_sharded():
    """Sharded engine on the CPU mesh under the same storm (smaller
    corpus: 8 shard_map programs share one core here)."""
    pytest.importorskip("jax")
    from maxmq_tpu.parallel.sharded import ShardedSigEngine, make_mesh

    idx = TopicIndex()
    _seed(idx, n=400, clients=60)
    eng = ShardedSigEngine(idx, mesh=make_mesh())
    checked, total, errors = _storm(eng, idx, duration_s=5, n_readers=2,
                                    batch_fn_name="subscribers_batch")
    assert not errors, errors
    assert total > 2 and checked > 0
