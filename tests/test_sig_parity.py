"""The signature matcher (grouped hash-equality) beyond the golden corpora
of test_golden_parity.py: its three device output forms (match words,
compact row stream, fixed slots) against the CPU reference trie on
randomized corpora, its depth windows and overflows, the kernel plan, and
the staleness overlay."""

import os
import random

import numpy as np
import pytest

from maxmq_tpu.matching import TopicIndex
from maxmq_tpu.matching.sig import SigEngine, compile_sig, tokenize_compact
from maxmq_tpu.protocol import Subscription

from matching_helpers import normalize, rand_corpus

PATHS = ["word", "compact", "fixed"]


@pytest.fixture(autouse=True)
def _always_device_path(monkeypatch):
    """These tests exist to exercise the DEVICE path; the ADR-008
    small-corpus router must not silently serve them from the trie
    (parity would pass vacuously)."""
    monkeypatch.setattr(SigEngine, "ROUTE_SUBS_MAX", -1)



def run_path(engine, path, topics):
    if path == "word":
        return engine.subscribers_batch(topics)
    if path == "compact":
        return engine.subscribers_compact_batch(topics)
    return engine.subscribers_fixed_batch(topics)


def check_parity(index, topics, paths=PATHS, **engine_kw):
    # both fixed-path device programs: fused Pallas kernel (auto) and the
    # XLA body (False)
    for use_pallas in ("auto", False):
        engine = SigEngine(index, use_pallas=use_pallas, **engine_kw)
        for path in paths:
            got = run_path(engine, path, topics)
            for topic, result in zip(topics, got):
                want = index.subscribers(topic)
                assert normalize(result) == normalize(want), (
                    f"[{path}/pallas={use_pallas}] mismatch on "
                    f"topic {topic!r}")
    return engine


def test_exact_rows_match_on_host():
    # exact-shape filters (full-literal AND '+') never occupy device
    # table width: both are host equality probes; the device carries
    # only the combinatorial '#'-prefix groups
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b/c"))
    idx.subscribe("c2", Subscription(filter="a/b/d"))
    idx.subscribe("c3", Subscription(filter="a/+/c"))
    idx.subscribe("c4", Subscription(filter="a/b/#"))
    engine = check_parity(idx, ["a/b/c", "a/b/d", "a/b", "a/b/c/d"])
    t = engine.tables
    assert sum(len(g.rows) for g in t.host_exact.values()) == 2
    assert sum(len(r) for p in t.host_plus.values()
               for r in p.rows) == 1
    # device rows: only the '#' filter (one group, one padded word)
    assert int(t.group_words.sum()) == 1


def test_mid_depth_filter_matches_via_compact_window():
    # deeper than the word path's max_levels but within the compact
    # DEPTH_CAP: the compact/fixed paths match it on device, the word
    # path falls back (its tokenizer flags the topic as overflow)
    idx = TopicIndex()
    mid_filter = "/".join(str(i) for i in range(20))
    idx.subscribe("c1", Subscription(filter=mid_filter))
    idx.subscribe("c2", Subscription(filter="a/b"))
    check_parity(idx, [mid_filter, "a/b"], max_levels=8)


def test_deep_filter_only_matches_overflow_topics():
    # beyond DEPTH_CAP (63 levels): compiled out of the device tables,
    # matched purely by the CPU fallback that overflow topics already take
    idx = TopicIndex()
    deep_filter = "/".join(str(i) for i in range(70))
    idx.subscribe("c1", Subscription(filter=deep_filter))
    idx.subscribe("c2", Subscription(filter="a/b"))
    engine = check_parity(idx, [deep_filter, "a/b"], max_levels=8)
    assert engine.tables.deep_rows


def test_fixed_slot_overflow_falls_back():
    idx = TopicIndex()
    for i in range(24):
        idx.subscribe(f"c{i}", Subscription(filter=f"x/{i}/+"))
        idx.subscribe(f"d{i}", Subscription(filter=f"+/{i}/y"))
    engine = SigEngine(idx)
    # topic matching >7 rows must still be exact via the CPU fallback
    idx2 = TopicIndex()
    for i in range(12):
        idx2.subscribe(f"c{i}", Subscription(filter=f"x/+/s{i}/#"))
        idx2.subscribe(f"e{i}", Subscription(filter="x/y/+/#"))
    engine2 = SigEngine(idx2)
    got = engine2.subscribers_fixed_batch(["x/y/s0/t"])[0]
    want = idx2.subscribers("x/y/s0/t")
    assert normalize(got) == normalize(want)


def test_tokenize_compact_encoding():
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b/c"))
    tables = compile_sig(idx)
    toks, lens, toks32, lengths = tokenize_compact(
        tables, ["a/b", "$SYS/x", "a/" + "/".join(["d"] * 80)])
    assert toks.dtype == np.uint8
    assert lens[0] == 2 and lens[1] == -2          # sign carries '$'
    assert abs(int(lens[2])) == 127                # too deep -> overflow
    assert lengths[0] == 2


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_randomized_parity(seed):
    rng = random.Random(seed)
    idx = TopicIndex()
    filters, topics = rand_corpus(rng, n_filters=120, n_clients=30)
    from maxmq_tpu.matching.topics import valid_filter
    for i, f in enumerate(filters):
        if not valid_filter(f):
            continue
        idx.subscribe(f"c{i % 30}",
                      Subscription(filter=f, qos=rng.randint(0, 2),
                                   identifier=rng.randint(0, 5)))
    check_parity(idx, topics)


def test_filter_matches_topic_rules():
    from maxmq_tpu.matching.topics import filter_matches_topic as fm
    assert fm(("a", "#"), ("a",), False)          # parent rule 4.7.1.2
    assert fm(("a", "#"), ("a", "b", "c"), False)
    assert not fm(("a", "#"), ("b",), False)
    assert fm(("+",), ("x",), False)
    assert not fm(("+",), ("x", "y"), False)
    assert not fm(("#",), ("$SYS",), True)        # [MQTT-4.7.2-1]
    assert not fm(("+", "x"), ("$SYS", "x"), True)
    assert fm(("$SYS", "#"), ("$SYS", "x"), True)
    assert fm(("a", "+", "c"), ("a", "", "c"), False)  # '+' matches empty


def test_pathological_group_count_falls_back_to_trie(monkeypatch):
    # corpora with more wildcard shapes than MAX_GROUPS must keep serving
    # exactly via the CPU trie — never raise on the publish hot path
    import maxmq_tpu.matching.sig as sigmod
    monkeypatch.setattr(sigmod, "MAX_GROUPS", 2)
    idx = TopicIndex()
    # only '#'-prefix shapes occupy device groups now; three distinct
    # ones exceed the patched limit
    idx.subscribe("c1", Subscription(filter="a/+/#"))
    idx.subscribe("c2", Subscription(filter="+/b/#"))
    idx.subscribe("c3", Subscription(filter="a/b/c/#"))
    idx.subscribe("c4", Subscription(filter="x/#"))
    engine = SigEngine(idx)
    for path in PATHS:
        got = run_path(engine, path, ["a/b/c", "x/y"])
        assert normalize(got[0]) == normalize(idx.subscribers("a/b/c"))
        assert normalize(got[1]) == normalize(idx.subscribers("x/y"))
    with pytest.raises(RuntimeError):
        engine.match_fixed(["a/b/c"])
    # corpus shrinks below the limit -> device path resumes
    idx.unsubscribe("c3", "a/b/c/#")
    idx.unsubscribe("c4", "x/#")
    monkeypatch.setattr(sigmod, "MAX_GROUPS", 4096)
    engine.refresh()
    assert engine._state[2] is not None


def test_pallas_multi_chunk_parity(monkeypatch):
    """Exercise the n_chunks > 1 branch of build_fixed_fn (cross-chunk
    candidate merge + short last chunk) by shrinking the chunk width —
    production corpora hit it at ~65K+ device rows."""
    from maxmq_tpu.matching import sig_pallas
    monkeypatch.setattr(sig_pallas, "CHUNK_WORDS", 128)
    rng = random.Random(5)
    idx = TopicIndex()
    segs = [f"s{i}" for i in range(40)]
    for i in range(12_000):
        depth = rng.randint(2, 6)
        levels = [rng.choice(segs) for _ in range(depth)]
        r = rng.random()
        if r < 0.15:
            levels[rng.randrange(depth)] = "+"
        elif r < 0.8:
            # mostly '#' shapes: only those occupy device words now
            if rng.random() < 0.5:
                levels[rng.randrange(depth)] = "+"
            levels = levels[:rng.randint(1, depth)] + ["#"]
        idx.subscribe(f"c{i}", Subscription(filter="/".join(levels),
                                            qos=i % 3))
    tables = compile_sig(idx)
    kplan = sig_pallas.plan(tables)
    assert kplan is not None and kplan["n_chunks"] > 1, kplan
    assert kplan["n_chunks"] * kplan["chunk"] >= kplan["w_pad"]
    topics = ["/".join(rng.choice(segs)
                       for _ in range(rng.randint(1, 7)))
              for _ in range(64)]
    engine = SigEngine(idx, use_pallas=True, fixed_max_rows=14)
    assert engine.pallas_active
    got = engine.subscribers_fixed_batch(topics)
    for topic, result in zip(topics, got):
        assert normalize(result) == normalize(idx.subscribers(topic)), topic


def test_pallas_plan_bounds():
    from maxmq_tpu.matching import sig_pallas
    idx = TopicIndex()
    for i in range(50):
        idx.subscribe(f"c{i}", Subscription(filter=f"a/{i}/+"))
    tables = compile_sig(idx)
    kplan = sig_pallas.plan(tables)
    assert kplan is not None and kplan["tb"] >= 32
    assert kplan["w_pad"] % 128 == 0
    # a 1M-sub-scale table set (tens of thousands of words) must still
    # plan — the batch tile shrinks instead of the kernel declining
    import numpy as np
    big = compile_sig(idx)
    big.group_words = np.asarray([12_000], dtype=np.int32)
    bplan = sig_pallas.plan(big)
    assert bplan is not None and bplan["tb"] >= 8
    # chunking keeps per-call VMEM bounded: even a 3M-word (96M-row)
    # table set plans, with chunk width capped and chunks covering w_pad
    huge = compile_sig(idx)
    huge.group_words = np.asarray([3_000_000], dtype=np.int32)
    hplan = sig_pallas.plan(huge)
    assert hplan is not None
    assert hplan["chunk"] <= sig_pallas.CHUNK_WORDS
    assert hplan["chunk"] * hplan["n_chunks"] >= hplan["w_pad"]


# ------------------------------------------------- staleness overlay

def _frozen_engine(idx, **kw):
    """Engine whose background recompile never runs: matches MUST be
    served exactly via the journal overlay."""
    engine = SigEngine(idx, **kw)
    engine.refresh_soon = lambda: None
    return engine


def test_overlay_serves_mutations_without_recompile():
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/+", qos=1))
    idx.subscribe("c2", Subscription(filter="a/b"))
    engine = _frozen_engine(idx)
    base_version = engine.tables.version

    idx.subscribe("c3", Subscription(filter="a/#", qos=2))          # add
    idx.unsubscribe("c2", "a/b")                                    # remove
    idx.subscribe("c1", Subscription(filter="a/+", qos=0))          # replace
    idx.subscribe("s1", Subscription(filter="$share/g/a/+"))        # shared

    for path in PATHS:
        got = run_path(engine, path, ["a/b", "a", "x"])
        for topic, s in zip(["a/b", "a", "x"], got):
            want = idx.subscribers(topic)
            assert normalize(s) == normalize(want), (path, topic)
    # tables never recompiled: served purely by the overlay
    assert engine.tables.version == base_version
    assert engine._overlay is not None and not engine._overlay.empty

    # a real refresh drops the overlay
    engine.refresh()
    assert engine.tables.version == idx.sub_version
    got = engine.subscribers_fixed_batch(["a/b"])[0]
    assert normalize(got) == normalize(idx.subscribers("a/b"))


def test_overlay_journal_gap_resyncs_via_trie(monkeypatch):
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b"))
    engine = _frozen_engine(idx)
    # overflow the journal far past its capacity
    idx._journal = type(idx._journal)(maxlen=4)
    for i in range(50):
        idx.subscribe(f"g{i}", Subscription(filter=f"q/{i}"))
    got = engine.subscribers_fixed_batch(["q/7", "a/b"])
    assert normalize(got[0]) == normalize(idx.subscribers("q/7"))
    assert normalize(got[1]) == normalize(idx.subscribers("a/b"))
    assert engine.fallbacks >= 2


def test_overlay_rebuilds_for_older_tables_after_newer_base():
    """Overlay reuse must key on the construction base, not the
    applied-through version: an overlay rebuilt against newer tables must
    not serve an in-flight batch still holding the old tables (the
    entries between the two versions would be replayed by neither)."""
    from maxmq_tpu.matching.sig import Overlay

    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b"))
    engine = _frozen_engine(idx)
    v_old = idx.sub_version

    idx.subscribe("c2", Subscription(filter="a/+"))     # entry in (old, new]
    v_new = idx.sub_version

    # simulate the race: a caller that already swapped to v_new tables
    # rebuilt the shared overlay with base v_new (it replays nothing)
    engine._overlay = Overlay(v_new)

    # an in-flight batch still holding v_old tables asks for its overlay:
    # it must see the (v_old, v_new] subscription
    ov = engine.overlay_for(v_old)
    assert ov is not None and ov != "resync"
    assert ("c2", "a/+") in ov.removed
    assert "c2" in ov.delta.subscribers("a/x").subscriptions


def test_add_row_out_of_range_is_dropped():
    """Padding-word artifacts past the row tables must be dropped, not
    raise IndexError on the publish hot path."""
    from maxmq_tpu.matching.trie import SubscriberSet

    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b"))
    engine = SigEngine(idx, auto_refresh=False)
    t = engine.tables
    res = SubscriberSet()
    SigEngine._add_row(res, len(t.row_levels) + 5, t, ["a", "b"], False)
    assert not res.subscriptions and not res.shared


def test_compact_max_rows_validated():
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b"))
    with pytest.raises(ValueError):
        SigEngine(idx, compact_max_rows=255)
    with pytest.raises(ValueError):
        SigEngine(idx, compact_max_rows=0)


def test_decode_rowset_cache_semantics():
    """The C decode pass memoizes results per verified row SET: topics
    with identical matched rows share one SubscriberSet object (the
    broker's own match cache already imposes the treat-as-immutable /
    deep_copy-before-mutating discipline). Parity with the trie must
    hold on both the first (building) and second (cache-hit) pass, and
    deep_copy must isolate."""
    from maxmq_tpu.native import decode_module

    rng = random.Random(9)
    alphabet = [f"t{i}" for i in range(6)]     # tiny: force hot rowsets
    idx = TopicIndex()
    for i in range(400):
        depth = rng.randint(1, 4)
        levels = [rng.choice(alphabet) for _ in range(depth)]
        r = rng.random()
        if r < 0.3:
            levels[rng.randrange(depth)] = "+"
        elif r < 0.5:
            levels = levels[: rng.randint(1, depth)] + ["#"]
        f = "/".join(levels)
        if rng.random() < 0.2:
            f = f"$share/g{rng.randint(0, 2)}/{f}"
        idx.subscribe(f"c{i}", Subscription(filter=f))
    engine = SigEngine(idx, auto_refresh=False)
    topics = ["/".join(rng.choice(alphabet)
                       for _ in range(rng.randint(1, 4)))
              for _ in range(256)]
    topics += topics[:64]                      # literal repeats too

    for _ in range(2):                         # pass 2 = pure cache hits
        got = engine.subscribers_batch(topics)
        for topic, g in zip(topics, got):
            assert normalize(g) == normalize(idx.subscribers(topic)), topic

    if decode_module() is None:
        return                                 # python fallback: no cache
    got = engine.subscribers_batch(topics)
    by_key = {}
    for topic, g in zip(topics, got):
        prev = by_key.setdefault(topic, g)
        assert prev is g or normalize(prev) == normalize(g)
    # repeated topics share the SAME object (cache hit), and deep_copy
    # isolates mutation
    rich = max(got, key=lambda s: len(s.subscriptions))
    if rich.subscriptions:
        cp = rich.deep_copy()
        cid = next(iter(cp.subscriptions))
        del cp.subscriptions[cid]
        assert cid in rich.subscriptions


def test_decode_rate_unit_bench():
    """VERDICT r1 #6: row -> SubscriberSet decode must sustain >= 1M
    rows/s — the per-delivery half that bounds fan-out no matter how
    fast the device matches. The batch path verifies all candidate pairs
    in one numpy pass and only unions verified entries in python."""
    import time

    rng = random.Random(5)
    alphabet = [f"s{i}" for i in range(50)]
    idx = TopicIndex()
    n = 20_000
    for i in range(n):
        depth = rng.randint(2, 6)
        levels = [rng.choice(alphabet) for _ in range(depth)]
        r = rng.random()
        if r < 0.3:
            levels[rng.randrange(depth)] = "+"
        elif r < 0.45:
            levels = levels[: rng.randint(1, depth)] + ["#"]
        idx.subscribe(f"c{i}", Subscription(filter="/".join(levels)))
    engine = SigEngine(idx, auto_refresh=False)
    topics = ["/".join(rng.choice(alphabet)
                       for _ in range(rng.randint(2, 6)))
              for _ in range(4096)]
    ctx = engine.dispatch_fixed(topics)
    got = engine.collect_fixed(topics, ctx)               # warm tables
    rows = sum(len(s.subscriptions) + len(s.shared) for s in got)
    best = 0.0
    for _ in range(5):                      # best-of: capability, not
        t0 = time.perf_counter()            # current machine load
        engine.collect_fixed(topics, ctx)   # fetch + verify + union only
        best = max(best, rows / (time.perf_counter() - t0))
    assert rows > 4096, "corpus produced too few matches to measure"
    if best < 1_000_000 and os.getloadavg()[0] > os.cpu_count() * 0.75:
        pytest.skip(f"box saturated (load {os.getloadavg()[0]:.1f}); "
                    f"measured {best:,.0f} rows/s — capability is "
                    "asserted on an idle box")
    assert best >= 1_000_000, f"decode rate {best:,.0f} rows/s < 1M"


def test_stream_prefetch_shortfall_fetches_rest(monkeypatch):
    """When the EMA hint under-predicts, the unprefetched tail of the
    row stream must be fetched synchronously — force tiny slices so the
    shortfall path actually runs."""
    import maxmq_tpu.matching.sig as sigmod

    idx = TopicIndex()
    for i in range(40):
        idx.subscribe(f"c{i}", Subscription(filter=f"a/{i}/#"))
        idx.subscribe(f"w{i}", Subscription(filter="a/+/x"))
    monkeypatch.setattr(sigmod, "_STREAM_CHUNK", 8)
    engine = SigEngine(idx, auto_refresh=False)
    engine._stream_rows_hint = 0        # prefetch just one tiny slice
    topics = [f"a/{i}/x" for i in range(40)]    # 2 rows per topic
    got = engine.subscribers_fixed_batch(topics)
    for i, (topic, s) in enumerate(zip(topics, got)):
        want = idx.subscribers(topic)
        assert set(s.subscriptions) == set(want.subscriptions), topic
    assert engine._stream_rows_hint > 0     # EMA updated from the batch


def test_retained_churn_never_recompiles():
    from maxmq_tpu.protocol.codec import PacketType as PT
    from maxmq_tpu.protocol.packets import FixedHeader, Packet
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/+"))
    engine = SigEngine(idx)
    v = engine.tables.version
    for i in range(5):
        idx.retain(Packet(fixed=FixedHeader(type=PT.PUBLISH),
                          topic=f"a/r{i}", payload=b"x"))
    assert idx.sub_version == v          # retained does not bump
    engine.refresh()
    assert engine.tables.version == v    # and never forces a recompile


def test_fixed_path_bucket_ladder_parity():
    """dispatch_fixed pads the batch axis to a sparse bucket ladder (16,
    powers of 4 to 4096, powers of 2 beyond). Batch sizes straddling the
    ladder edges must decode identically to the trie — pad rows are
    depth-1 '$'-topics that may match nothing (round-3 bucketing)."""
    rng = random.Random(11)
    filters, _ = rand_corpus(rng, 300, 40)
    idx = TopicIndex()
    for i, f in enumerate(filters):
        idx.subscribe(f"cl-{i % 40}", Subscription(filter=f, qos=i % 3))
    engine = SigEngine(idx, auto_refresh=False)
    alphabet = [f"t{i}" for i in range(8)]
    for size in (1, 15, 16, 17, 63, 64, 65, 255, 257):
        topics = ["/".join(rng.choice(alphabet)
                           for _ in range(rng.randint(1, 5)))
                  for _ in range(size)]
        got = engine.subscribers_fixed_batch(topics)
        assert len(got) == size
        for topic, result in zip(topics, got):
            want = idx.subscribers(topic)
            assert normalize(result) == normalize(want), (size, topic)


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_randomized_churn_parity(seed):
    """Subscribe/unsubscribe churn interleaved with fixed-path matches:
    every match must agree with the trie REGARDLESS of where the engine
    is in its overlay/journal/recompile lifecycle (forced rotations and
    overlay-served windows both exercised)."""
    rng = random.Random(seed)
    filters, topics = rand_corpus(rng, 250, 40)
    idx = TopicIndex()
    live: list[tuple[str, str]] = []
    for i, f in enumerate(filters[:120]):
        cid = f"cl-{i % 40}"
        idx.subscribe(cid, Subscription(filter=f, qos=i % 3))
        live.append((cid, f))
    engine = SigEngine(idx, auto_refresh=False)
    pool = filters[120:]
    for step in range(60):
        op = rng.random()
        if op < 0.4 and pool:
            cid = f"cl-{rng.randrange(40)}"
            f = pool.pop(rng.randrange(len(pool)))
            idx.subscribe(cid, Subscription(filter=f,
                                            qos=rng.randrange(3)))
            live.append((cid, f))
        elif op < 0.7 and live:
            cid, f = live.pop(rng.randrange(len(live)))
            idx.unsubscribe(cid, f)
        if rng.random() < 0.25:
            engine.refresh(force=True)      # rotation mid-churn
        batch = [rng.choice(topics) for _ in range(rng.randint(1, 9))]
        got = engine.subscribers_fixed_batch(batch)
        for topic, result in zip(batch, got):
            want = idx.subscribers(topic)
            assert normalize(result) == normalize(want), (seed, step,
                                                          topic)


# --------------------------------------------------------------------
# DeliveryIntents (ADR 007): the fan-out-ready native decode form
# --------------------------------------------------------------------

def _intents_engine(idx, **kw):
    eng = SigEngine(idx, **kw)
    eng.emit_intents = True
    return eng


def _native_mod():
    from maxmq_tpu.native import decode_module
    mod = decode_module()
    if mod is None or not hasattr(mod, "DeliveryIntents"):
        pytest.skip("maxmq_decode extension unavailable")
    return mod


def _saved_chain_params(mod) -> tuple:
    """Chain params in effect, restored verbatim by finally blocks
    (never the hardcoded defaults — ADVICE r5 #3)."""
    from maxmq_tpu.native import chain_params_in_effect
    return chain_params_in_effect(mod)


def _as_set(result):
    to_set = getattr(result, "to_set", None)
    return to_set() if to_set is not None else result


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_intents_parity_randomized(seed):
    """Intents (iterated AND via to_set) match the CPU trie on the same
    randomized corpora the set path is held to."""
    mod = _native_mod()
    rng = random.Random(seed)
    idx = TopicIndex()
    filters, topics = rand_corpus(rng, n_filters=150, n_clients=40)
    from maxmq_tpu.matching.topics import valid_filter
    for i, f in enumerate(filters):
        if not valid_filter(f):
            continue
        idx.subscribe(f"c{i % 40}",
                      Subscription(filter=f, qos=rng.randint(0, 2),
                                   identifier=rng.randint(0, 5)))
    eng = _intents_engine(idx)
    ctx = eng.dispatch_fixed(topics)
    got = eng.collect_fixed(topics, ctx)
    saw_intents = 0
    for topic, result in zip(topics, got):
        want = idx.subscribers(topic)
        if isinstance(result, mod.DeliveryIntents):
            saw_intents += 1
            # iteration surface agrees with the materialized set
            by_iter = {cid: sub for cid, sub in result}
            assert set(by_iter) == set(want.subscriptions), topic
            for cid, sub in by_iter.items():
                w = want.subscriptions[cid]
                assert sub.qos == w.qos, (topic, cid)
                assert dict(sub.identifiers) == dict(w.identifiers), \
                    (topic, cid)
                assert result.resolve({cid: cid})[0] == [(cid, sub)]
            assert result.resolve({"no-such-client": 0})[0] == []
            assert len(result) == len(want.subscriptions) + sum(
                len(m) for m in want.shared.values())
        assert normalize(_as_set(result)) == normalize(want), topic
    assert saw_intents, "native intents path never engaged"


def test_intents_rowset_cache_identity():
    """Repeated topics resolve to the SAME cached intents object (the
    whole point: zero construction on the hot repeat path)."""
    _native_mod()
    idx = TopicIndex()
    for i in range(50):
        idx.subscribe(f"c{i}", Subscription(filter="hot/#", qos=1))
    eng = _intents_engine(idx)
    t = ["hot/x"] * 8 + ["hot/y"] * 8
    got = eng.collect_fixed(t, eng.dispatch_fixed(t))
    assert got[0] is got[7], "same topic should alias one cached object"
    assert got[0] is got[8], "same ROW SET should alias too"
    # to_set is cached on the object
    assert got[0].to_set() is got[0].to_set()


def test_intents_empty_and_shared_surface():
    _native_mod()
    idx = TopicIndex()
    idx.subscribe("s1", Subscription(filter="$share/g/sh/+", qos=1))
    idx.subscribe("p1", Subscription(filter="sh/+", qos=2))
    eng = _intents_engine(idx)
    t = ["sh/a", "nomatch/zz"]
    got = eng.collect_fixed(t, eng.dispatch_fixed(t))
    r, empty = got
    assert ("g", "$share/g/sh/+") in r.shared
    # a $share member is a candidate, never a plain entry
    assert [c for c, _s in r.resolve({"p1": "p1", "s1": "s1"})[0]] == ["p1"]
    assert len(empty) == 0 and list(empty) == []
    assert empty.shared == {}


def test_intents_overlay_window_degrades_to_sets():
    """During a journal overlay window results must carry the mutation
    (merge_delta needs set semantics); parity must hold throughout."""
    _native_mod()
    idx = TopicIndex()
    for i in range(40):
        idx.subscribe(f"c{i}", Subscription(filter=f"ov/{i}/#", qos=1))
    eng = _frozen_engine(idx)          # no auto recompile
    eng.emit_intents = True
    idx.subscribe("late", Subscription(filter="ov/1/#", qos=2))
    t = ["ov/1/x"]
    got = eng.collect_fixed(t, eng.dispatch_fixed(t))
    want = idx.subscribers("ov/1/x")
    assert normalize(_as_set(got[0])) == normalize(want)
    assert "late" in _as_set(got[0]).subscriptions


def test_intents_chained_base_parity():
    """Fat-row topics build CHAINED intents (immutable single-row base +
    per-topic tail with slot overrides) — the cold-stream wall killer.
    Every consumer surface must agree with the trie: iteration (dedup,
    merged qos/identifiers), n, len, resolve, to_set, $share maps."""
    _native_mod()
    idx = TopicIndex()
    # fat '#' bucket well past g_chain_min_base (default 64,
    # native/maxmq_decode.cpp)
    for i in range(150):
        idx.subscribe(f"fat{i}", Subscription(filter="iot/dev/#", qos=1))
    # thin rows; fat3/fat5 overlap the fat row -> overrides (merged
    # qos max + v5 identifier union); solo* are pure tail entries
    idx.subscribe("fat3", Subscription(filter="iot/dev/a/b", qos=2,
                                       identifier=7))
    idx.subscribe("fat5", Subscription(filter="iot/dev/+/b", qos=0))
    idx.subscribe("solo1", Subscription(filter="iot/dev/a/b", qos=2))
    idx.subscribe("solo2", Subscription(filter="iot/dev/+/b", qos=1,
                                        identifier=3))
    idx.subscribe("sh1", Subscription(filter="$share/g/iot/dev/#", qos=1))
    idx.subscribe("sh2", Subscription(filter="$share/g/iot/dev/a/b",
                                      qos=1))
    eng = _intents_engine(idx)
    eng.route_small = False
    topics = ["iot/dev/a/b",   # chain: 2 tail entries + 2 overrides
              "iot/dev/x/b",   # chain: 1 tail + 1 override
              "iot/dev/z",     # single fat row: plain (not chained)
              "nope/x"]        # empty
    got = eng.collect_fixed(topics, eng.dispatch_fixed(topics))
    assert got[0].chained and got[1].chained
    assert not got[2].chained and not got[3].chained
    for topic, r in zip(topics, got):
        want = idx.subscribers(topic)
        by_iter = {}
        for cid, sub in r:
            assert cid not in by_iter, f"dup {cid} on {topic}"
            by_iter[cid] = sub
        assert len(by_iter) == r.n, topic
        assert set(by_iter) == set(want.subscriptions), topic
        for cid, sub in by_iter.items():
            w = want.subscriptions[cid]
            assert sub.qos == w.qos, (topic, cid)
            assert dict(sub.identifiers) == dict(w.identifiers), \
                (topic, cid)
            assert r.resolve({cid: cid})[0] == [(cid, sub)]
        assert r.resolve({"no-such-client": 0})[0] == []
        assert len(r) == len(want.subscriptions) + sum(
            len(m) for m in want.shared.values()), topic
        assert normalize(r.to_set()) == normalize(want), topic
    # chains are cached per row set and alias across topics
    again = eng.collect_fixed(topics, eng.dispatch_fixed(topics))
    assert again[0] is got[0] and again[1] is got[1]


def test_intents_chained_randomized_fat_corpus():
    """Randomized corpora with fat '#' buckets: chained vs trie parity
    over many distinct row sets (cold-stream shape)."""
    _native_mod()
    rng = random.Random(99)
    idx = TopicIndex()
    for i in range(200):
        idx.subscribe(f"f{i}", Subscription(filter="b/#",
                                            qos=rng.randint(0, 2)))
    # thin overlapping filters, some reusing fat clients
    for i in range(60):
        cid = f"f{rng.randrange(200)}" if i % 3 else f"solo{i}"
        seg = rng.choice(["b/x", "b/+", f"b/{i}", f"b/x/{i}", "b/+/+"])
        idx.subscribe(cid, Subscription(filter=seg,
                                        qos=rng.randint(0, 2),
                                        identifier=rng.randint(0, 4)))
    eng = _intents_engine(idx)
    eng.route_small = False
    topics = [rng.choice(["b/x", "b/q", f"b/{i}", f"b/x/{i}",
                          f"b/{i}/z"]) for i in range(120)]
    got = eng.collect_fixed(topics, eng.dispatch_fixed(topics))
    saw_chain = 0
    for topic, r in zip(topics, got):
        want = idx.subscribers(topic)
        saw_chain += bool(getattr(r, "chained", False))
        by_iter = {}
        for cid, sub in r:
            assert cid not in by_iter, (topic, cid)
            by_iter[cid] = sub
        assert len(by_iter) == r.n, topic
        assert normalize(r.to_set()) == normalize(want), topic
        for cid, sub in by_iter.items():
            w = want.subscriptions[cid]
            assert (sub.qos, dict(sub.identifiers)) == \
                (w.qos, dict(w.identifiers)), (topic, cid)
    assert saw_chain, "chained path never engaged"


def test_intents_chained_equals_full_union_flags():
    """A chained union must be INDISTINGUISHABLE from the full union of
    the same row sets — including the flag fields normalize() ignores
    (merge_subscription takes no_local/RAP/RH from the newer filter, so
    a naive chain would reverse the donor when the fat row anchors
    first). Full-field A/B via the test-only _set_chain_enabled."""
    mod = _native_mod()
    if not hasattr(mod, "_set_chain_enabled"):
        pytest.skip("chain toggle unavailable")

    def build_engine():
        idx = TopicIndex()
        for i in range(150):
            idx.subscribe(f"fat{i}", Subscription(
                filter="fl/dev/#", qos=1, retain_handling=0))
        # overlapping clients with DISTINCT flag values per filter
        idx.subscribe("fat3", Subscription(
            filter="fl/dev/a/b", qos=2, retain_handling=2,
            no_local=True, identifier=7))
        idx.subscribe("fat5", Subscription(
            filter="fl/dev/+/b", qos=0, retain_as_published=True,
            retain_handling=1))
        idx.subscribe("fat7", Subscription(
            filter="fl/+/a/b", qos=1, retain_handling=2, identifier=2))
        eng = _intents_engine(idx)
        eng.route_small = False
        return eng

    topics = ["fl/dev/a/b", "fl/dev/x/b", "fl/dev/z/q"]

    def snapshot(eng):
        got = eng.collect_fixed(topics, eng.dispatch_fixed(topics))
        out = []
        for r in got:
            out.append(sorted(
                (cid, s.filter, s.qos, s.no_local,
                 s.retain_as_published, s.retain_handling,
                 s.identifier, tuple(sorted(s.identifiers.items())))
                for cid, s in r))
        return got, out

    try:
        chained_res, chained = snapshot(build_engine())
        assert any(getattr(r, "chained", False) for r in chained_res)
        mod._set_chain_enabled(False)
        plain_res, plain = snapshot(build_engine())
        assert not any(getattr(r, "chained", False) for r in plain_res)
    finally:
        mod._set_chain_enabled(True)
    assert chained == plain


@pytest.mark.parametrize("seed", [41, 42, 43, 44])
def test_intents_chain_fuzz_equivalence(seed):
    """Randomized full-field equivalence: for corpora with several fat
    buckets, overlapping thin filters, v5 identifiers and $share, the
    chained build must equal the full union on EVERY field of every
    delivered record, for every topic (not just the normalize
    projection)."""
    mod = _native_mod()
    if not hasattr(mod, "_set_chain_params"):
        pytest.skip("chain toggle unavailable")
    rng = random.Random(seed)

    def build_engine():
        idx = TopicIndex()
        for b in range(rng.randint(1, 3)):
            root = rng.choice(["fz", "fz/x", "deep/fz"])
            for i in range(rng.randint(70, 140)):
                idx.subscribe(f"b{b}c{i}", Subscription(
                    filter=f"{root}/#", qos=rng.randint(0, 2),
                    retain_handling=rng.randint(0, 2)))
        for i in range(rng.randint(10, 40)):
            cid = (f"b0c{rng.randrange(70)}" if i % 2 else f"s{i}")
            f = rng.choice(["fz/+", "fz/x/+", "fz/x/a", f"fz/t{i}",
                            "deep/fz/+/q", "$share/g/fz/#",
                            "fz/x/a/b"])
            idx.subscribe(cid, Subscription(
                filter=f, qos=rng.randint(0, 2),
                no_local=bool(rng.getrandbits(1)),
                retain_as_published=bool(rng.getrandbits(1)),
                identifier=rng.randint(0, 6)))
        eng = _intents_engine(idx)
        eng.route_small = False
        return eng

    topics = [rng.choice(["fz/x/a", "fz/x/a/b", "fz/q", "fz/x/zz",
                          f"fz/t{rng.randrange(40)}", "deep/fz/m/q",
                          "fz/x/a/b/c", "none/x"]) for _ in range(60)]

    def snapshot(eng):
        got = eng.collect_fixed(topics, eng.dispatch_fixed(topics))
        out = []
        for r in got:
            s = r.to_set() if hasattr(r, "to_set") else r
            out.append((sorted(
                (cid, v.filter, v.qos, v.no_local,
                 v.retain_as_published, v.retain_handling, v.identifier,
                 tuple(sorted(v.identifiers.items())))
                for cid, v in s.subscriptions.items()),
                sorted((g, f, tuple(sorted(m)))
                       for (g, f), m in s.shared.items())))
        return got, out

    state = rng.getstate()
    saved = _saved_chain_params(mod)
    try:
        mod._set_chain_params(32, 1, 1)    # chain aggressively
        chained_res, chained = snapshot(build_engine())
        assert any(getattr(r, "chained", False) for r in chained_res)
        mod._set_chain_enabled(False)
        rng.setstate(state)                # identical corpus
        _, plain = snapshot(build_engine())
    finally:
        mod._set_chain_enabled(True)
        mod._set_chain_params(*saved)
    assert chained == plain


def test_table_release_breaks_cycle_on_rotation():
    """Dropping a compiled snapshot must release its cached intents:
    the capsule<->icache cycle is not GC-collectible (VERDICT: leak
    would grow per subscription rotation)."""
    import gc
    import weakref
    mod = _native_mod()
    idx = TopicIndex()
    for i in range(30):
        idx.subscribe(f"c{i}", Subscription(filter=f"rl/{i}", qos=0))
    eng = _intents_engine(idx)
    t = [f"rl/{i}" for i in range(30)]
    got = eng.collect_fixed(t, eng.dispatch_fixed(t))
    tables = eng.tables
    tref = weakref.ref(tables)
    del got, tables
    # rotation: force a recompile; the old snapshot is dropped
    idx.subscribe("newcl", Subscription(filter="rl/0", qos=1))
    eng.refresh(force=True)
    for _ in range(3):
        gc.collect()
    assert tref() is None, "old snapshot still alive after rotation"


# ---------------------------------------------------------------------------
# Device-free host match (subscribers_host_batch): the batcher's
# low-occupancy bypass path — exact/'+'/'#' signature probes + the same
# C decode, no device dispatch at all.


def _native_host_built() -> bool:
    from maxmq_tpu.native import available, decode_module
    mod = decode_module()
    return available() and mod is not None and hasattr(mod, "host_batch")


# a topic deeper than the tokenizer keeps: the trie answers it
_DEEP_TOPIC = "/".join(["t1"] * 130)
# one client's overlapping filters (the tree's shape), folded by
# merge_subscription, and the topics that reach them
_FOLD_FILTERS = ("t1/#", "t1/+/t2", "t1/t3/t2", "+/t3/#")
_HOST_EXTRAS = [_DEEP_TOPIC, "t1/t3/t2", "$t1/t3/t2", "t1/t3"]

# (seed, batch size: None = the corpus's own topics, overlay window)
_HOST_CASES = [
    pytest.param(31, None, False, id="31"),
    pytest.param(32, None, False, id="32"),
    pytest.param(33, None, False, id="33"),
    pytest.param(34, 0, False, id="batch0"),
    pytest.param(35, 1, False, id="batch1"),
    pytest.param(36, 2, False, id="batch2"),
    pytest.param(37, 256, False, id="batch256"),
    pytest.param(38, 512, False, id="batch512"),    # the bypass's cap
    pytest.param(39, None, True, id="overlay"),
]


@pytest.mark.parametrize("seed, batch, overlay", _HOST_CASES)
def test_host_batch_parity_randomized(seed, batch, overlay):
    """The host-only path (one native call where the libraries are
    built) matches the trie and the device path's set form exactly, in
    both result forms: '$' topics, a topic too deep for the tokenizer
    (a fallback), $share maps, one client's overlapping filters, and an
    open overlay window (set form + merge_delta)."""
    rng = random.Random(seed)
    idx = TopicIndex()
    filters, topics = rand_corpus(rng, n_filters=150, n_clients=40)
    from maxmq_tpu.matching.topics import valid_filter
    for i, f in enumerate(filters):
        if not valid_filter(f):
            continue
        idx.subscribe(f"c{i % 40}",
                      Subscription(filter=f, qos=rng.randint(0, 2),
                                   identifier=rng.randint(0, 5)))
    for k, f in enumerate(_FOLD_FILTERS):
        idx.subscribe("fold", Subscription(filter=f, qos=k % 3,
                                           identifier=k + 1))
    pool = topics + _HOST_EXTRAS
    topics = (pool if batch is None else
              (_HOST_EXTRAS + [rng.choice(pool) for _ in range(batch)])
              [:batch])
    fused = _native_host_built()
    for emit in (False, True):
        eng = SigEngine(idx, auto_refresh=not overlay)
        eng.emit_intents = emit
        if overlay:
            # newer than the compiled tables: an add, a replace, and
            # removals, shared-group members among them
            idx.subscribe("late", Subscription(filter="t1/+/t2", qos=1))
            idx.subscribe("fold", Subscription(filter="t1/#", qos=2))
            idx.unsubscribe("fold", "+/t3/#")
            idx.subscribe("c7", Subscription(filter="$share/g1/t1/#"))
            for f in filters[:20]:
                idx.unsubscribe(f"c{filters.index(f) % 40}", f)
            assert eng.overlay_for(eng.tables.version) not in (None,
                                                               "resync")
        got = eng.subscribers_host_batch(topics)
        assert eng.host_fused_batches == (1 if fused else 0)
        assert len(got) == len(topics)
        device = eng.subscribers_fixed_batch(topics) if topics else []
        for topic, result, dev in zip(topics, got, device):
            want = normalize(idx.subscribers(topic))
            assert normalize(_as_set(result)) == want, (topic, emit)
            assert normalize(_as_set(dev)) == want, (topic, emit)
        deep = topics.count(_DEEP_TOPIC)
        assert eng.host_matches == len(topics) - deep
        assert eng.fallbacks == 2 * deep       # the host and the device
        if overlay:
            # put the index back for the other result form
            idx.unsubscribe("late", "t1/+/t2")
            idx.subscribe("fold", Subscription(filter="t1/#", qos=0,
                                               identifier=1))
            idx.subscribe("fold", Subscription(filter="+/t3/#", qos=0,
                                               identifier=4))
            idx.unsubscribe("c7", "$share/g1/t1/#")
            for f in filters[:20]:
                if valid_filter(f):
                    idx.subscribe(f"c{filters.index(f) % 40}",
                                  Subscription(filter=f))


def test_host_batch_phases_and_the_numpy_path(monkeypatch):
    """Under a batch record the one native call writes match_prep /
    match_probe / match_decode, in order and inside match_host; with
    the native libraries missing the numpy path answers the same, and
    the fused counter does not move."""
    if not _native_host_built():
        pytest.skip("native libraries unavailable")
    from maxmq_tpu import native
    from maxmq_tpu.trace import BatchRecord, PipelineTracer

    rng = random.Random(40)
    idx = TopicIndex()
    filters, topics = rand_corpus(rng, n_filters=150, n_clients=40)
    from maxmq_tpu.matching.topics import valid_filter
    for i, f in enumerate(filters):
        if valid_filter(f):
            idx.subscribe(f"c{i % 40}", Subscription(filter=f, qos=i % 3))
    topics = topics + _HOST_EXTRAS
    tracer = PipelineTracer(sample_n=1)
    eng = SigEngine(idx)
    rec = BatchRecord(tracer, 0, len(topics))
    h0 = tracer.clock()
    got = rec.run(eng.subscribers_host_batch, topics)
    rec.phase("match_host", h0, tracer.clock())
    assert eng.host_fused_batches == 1
    ph = {name: (t0, t1) for name, t0, t1, _on in rec.phases}
    assert set(ph) == {"match_host", "match_prep", "match_probe",
                       "match_decode"}
    (p0, p1), (q0, q1), (d0, d1) = (ph["match_prep"], ph["match_probe"],
                                    ph["match_decode"])
    assert h0 <= p0 <= p1 <= q0 <= q1 <= d0 <= d1 <= ph["match_host"][1]
    # untraced: nothing is timed, nothing is written
    assert eng.subscribers_host_batch(topics[:3]) is not None
    assert eng.host_fused_batches == 2

    monkeypatch.setattr(native, "_try_load", lambda: None)
    monkeypatch.setattr(native, "decode_module", lambda build=True: None)
    plain = SigEngine(idx)
    rec = BatchRecord(tracer, 1, len(topics))
    again = rec.run(plain.subscribers_host_batch, topics)
    assert plain.host_fused_batches == 0
    assert {name for name, *_ in rec.phases} == {
        "match_prep", "match_probe", "match_decode"}
    for topic, a, b in zip(topics, got, again):
        assert normalize(_as_set(a)) == normalize(_as_set(b)) == \
            normalize(idx.subscribers(topic)), topic


def test_host_batch_never_touches_device(monkeypatch):
    """The host path must stay correct with the device program broken —
    that independence is exactly what the bypass relies on when the
    link is degraded."""
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="h/b/c", qos=1))
    idx.subscribe("c2", Subscription(filter="h/+/c", qos=2))
    idx.subscribe("c3", Subscription(filter="h/#"))
    idx.subscribe("c4", Subscription(filter="#"))
    idx.subscribe("c5", Subscription(filter="$share/g/h/#"))
    eng = SigEngine(idx)
    eng.refresh(force=True)

    def boom(*a, **k):
        raise AssertionError("device program invoked on the host path")

    monkeypatch.setattr(eng, "dispatch_fixed", boom)
    state = list(eng._state)
    state[6] = boom                      # the jitted fixed program
    eng._state = tuple(state)
    topics = ["h/b/c", "h/x/c", "h", "h/deep/er/still", "x", "$SYS/x"]
    got = eng.subscribers_host_batch(topics)
    for topic, result in zip(topics, got):
        assert normalize(_as_set(result)) == \
            normalize(idx.subscribers(topic)), topic


def test_single_topic_surface_serves_from_host():
    """engine.subscribers() never touches the device: trie below the
    measured corpus crossover, the device-free host path above it."""
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="s/+/t", qos=1))
    eng = SigEngine(idx)
    eng.route_small = False
    # small corpus: trie (its walk undercuts the host call's fixed cost)
    got = eng.subscribers(topic="s/x/t")
    assert "c1" in _as_set(got).subscriptions
    assert eng.host_matches == 0
    # past the crossover: the host path
    eng.HOST_SINGLE_SUBS_MIN = 0
    got = eng.subscribers(topic="s/x/t")
    assert "c1" in _as_set(got).subscriptions
    assert eng.host_matches == 1


def test_intents_multi_base_composition():
    """Round-5 multi-base chains: a row set holding several DISJOINT
    fat rows composes per-row cached bases (fat-row combinations never
    repeat on cold streams, but each row does — measured in
    BASELINE-COMPARE) and must stay full-field-identical to both the
    legacy single-fattest-base form and the full union. A client
    subscribed into TWO fat rows makes both rows impure: at most one
    may anchor, and parity must still hold."""
    mod = _native_mod()
    if not hasattr(mod, "_set_multi_base"):
        pytest.skip("multi-base toggle unavailable")

    def build_engine():
        idx = TopicIndex()
        # three fat buckets all matching mb/x/a/b
        for i in range(90):
            idx.subscribe(f"fa{i}", Subscription(filter="mb/#", qos=1))
        for i in range(40):
            idx.subscribe(f"fb{i}", Subscription(
                filter="mb/x/#", qos=0, retain_handling=1))
        for i in range(24):
            idx.subscribe(f"fc{i}", Subscription(filter="mb/x/a/#",
                                                 qos=2))
        # impure pair: one client delivering from TWO fat rows
        idx.subscribe("fa0", Subscription(filter="mb/x/#", qos=2,
                                          no_local=True))
        # thin tail incl. a base-collision override with v5 identifier
        idx.subscribe("thin1", Subscription(filter="mb/x/a/b", qos=1))
        idx.subscribe("fb3", Subscription(filter="mb/+/a/b", qos=2,
                                          identifier=5))
        eng = _intents_engine(idx)
        eng.route_small = False
        return eng

    topics = ["mb/x/a/b", "mb/x/a/c", "mb/q", "mb/x/zz"]

    def snapshot(eng):
        got = eng.collect_fixed(topics, eng.dispatch_fixed(topics))
        out = []
        for r in got:
            s = r.to_set() if hasattr(r, "to_set") else r
            out.append((sorted(
                (cid, v.filter, v.qos, v.no_local,
                 v.retain_as_published, v.retain_handling, v.identifier,
                 tuple(sorted(v.identifiers.items())))
                for cid, v in s.subscriptions.items()),
                sorted((g, f, tuple(sorted(m)))
                       for (g, f), m in s.shared.items())))
        return got, out

    def max_bases(results):
        best = 0
        for r in results:
            rep = repr(r)
            if "bases=" in rep:
                best = max(best, int(rep.split("bases=")[1].split(",")[0]))
        return best

    saved = _saved_chain_params(mod)
    try:
        mod._set_chain_params(32, 4, 1)
        multi_res, multi = snapshot(build_engine())
        assert max_bases(multi_res) >= 2, \
            [repr(r) for r in multi_res]
        mod._set_multi_base(False)
        single_res, single = snapshot(build_engine())
        assert max_bases(single_res) <= 1
        mod._set_chain_enabled(False)
        _, plain = snapshot(build_engine())
    finally:
        mod._set_chain_enabled(True)
        mod._set_multi_base(True)
        mod._set_chain_params(*saved)
    assert multi == plain
    assert single == plain


# --------------------------------------------------------------------
# resolve(registry): a match result against the client registry in one
# pass (ADR 007) — the fan-out walks only entries with a session
# --------------------------------------------------------------------


class _Session:
    """Stands for a Client: resolve pairs an entry with whatever value
    the registry dict holds."""

    def __init__(self, cid: str) -> None:
        self.id = cid


def _fuzz_index(rng, overlap: bool) -> TopicIndex:
    """The corpus shape of test_intents_chain_fuzz_equivalence: fat '#'
    buckets (the first the fattest, so it anchors a single-base chain),
    thin filters, v5 identifiers and $share groups. With ``overlap``
    half the thin filters belong to clients of the fattest bucket:
    their merged records become slot overrides of the chain."""
    idx = TopicIndex()
    for b in range(rng.randint(2, 3)):
        root = ["fz", "fz/x", "deep/fz"][b]
        for i in range(140 if b == 0 else rng.randint(70, 120)):
            idx.subscribe(f"b{b}c{i}", Subscription(
                filter=f"{root}/#", qos=rng.randint(0, 2)))
    for i in range(40):
        cid = (f"b0c{rng.randrange(70)}" if overlap and i % 2
               else f"s{i}")
        f = rng.choice(["fz/+", "fz/x/+", "fz/x/a", f"fz/t{i}",
                        "deep/fz/+/q", "$share/g/fz/#",
                        "$share/h/fz/x/+", "fz/x/a/b"])
        idx.subscribe(cid, Subscription(
            filter=f, qos=rng.randint(0, 2),
            identifier=rng.randint(0, 6)))
    return idx


_FUZZ_TOPICS = ["fz/x/a", "fz/x/a/b", "fz/q", "fz/x/zz", "fz/t3",
                "deep/fz/m/q", "fz/x/a/b/c", "none/x"]


def _resolve_results(shape: str, rng) -> list:
    """Match results of one shape over the fuzz corpus. The chain
    toggles are restored before returning: a built result keeps its
    form."""
    from maxmq_tpu.matching.trie import _PySubscriberSet
    idx = _fuzz_index(rng, overlap=shape != "chained_intents")
    if shape == "trie_set":
        out = []
        for t in _FUZZ_TOPICS:
            r = idx.subscribers(t)
            out.append(_PySubscriberSet(dict(r.subscriptions),
                                        dict(r.shared)))
        return out
    mod = _native_mod()
    if shape == "native_set":
        out = [idx.subscribers(t) for t in _FUZZ_TOPICS]
        assert all(type(r) is mod.SubscriberSet for r in out)
        return out
    eng = _intents_engine(idx)
    eng.route_small = False
    saved = _saved_chain_params(mod)
    try:
        if shape == "plain_intents":
            mod._set_chain_enabled(False)
        else:
            mod._set_chain_params(32, 4, 1)
            mod._set_multi_base(shape == "multi_base_intents")
        got = eng.collect_fixed(_FUZZ_TOPICS,
                                eng.dispatch_fixed(_FUZZ_TOPICS))
    finally:
        mod._set_chain_enabled(True)
        mod._set_multi_base(True)
        mod._set_chain_params(*saved)
    assert all(isinstance(r, mod.DeliveryIntents) for r in got)
    reprs = [repr(r) for r in got]
    if shape == "plain_intents":
        assert not any(r.chained for r in got)
    elif shape == "chained_intents":
        assert any("bases=1," in x for x in reprs), reprs
    elif shape == "multi_base_intents":
        assert any("bases=2," in x or "bases=3," in x for x in reprs), reprs
    else:
        assert shape == "override_intents"
        assert any(r.chained and "overrides=0" not in x
                   for r, x in zip(got, reprs)), reprs
    return got


def _entries(result) -> list:
    if hasattr(result, "to_set"):
        return list(result)
    return list(result.subscriptions.items())


def _registry_for(kind: str, results, rng) -> dict:
    cids = sorted({cid for r in results for cid, _s in _entries(r)}
                  | {cid for r in results for m in r.shared.values()
                     for cid in m})
    if kind == "empty":
        keep = []
    elif kind == "all":
        keep = cids
    elif kind == "one_percent":
        keep = rng.sample(cids, max(1, len(cids) // 100))
    else:
        assert kind == "half"
        keep = rng.sample(cids, len(cids) // 2)
    # sessions the table does not know must be harmless too
    return {cid: _Session(cid) for cid in keep + ["not-in-the-table"]}


def _check_resolved(result, reg: dict) -> None:
    entries = _entries(result)
    before = [(cid, id(sub)) for cid, sub in entries]
    pairs, shared, matched, resolved = result.resolve(reg)
    want = [(reg[cid], sub) for cid, sub in entries if cid in reg]
    assert len(pairs) == len(want)
    for (gc, gs), (wc, ws) in zip(pairs, want):
        assert gc is wc and gs is ws
    want_shared = {k: m for k, m in result.shared.items()
                   if any(cid in reg for cid in m)}
    assert list(shared) == list(want_shared)        # order too
    for k, m in shared.items():
        assert m is result.shared[k]        # the member map, whole
    assert matched == len(result)
    assert resolved == len(want) + sum(
        cid in reg for m in result.shared.values() for cid in m)
    # nothing was written onto the (shared, cached) result
    assert [(cid, id(sub)) for cid, sub in _entries(result)] == before


@pytest.mark.parametrize("registry",
                         ["empty", "all", "one_percent", "half"])
@pytest.mark.parametrize("shape", [
    "plain_intents", "chained_intents", "multi_base_intents",
    "override_intents", "native_set", "trie_set"])
def test_resolve_parity(shape, registry):
    """For every result shape and registry, the resolved pairs are the
    entries with a session, in iteration order, each paired with the
    registry's value, and the cut $share map is the keys with a
    registered candidate."""
    rng = random.Random(f"{shape}/{registry}")
    results = _resolve_results(shape, rng)
    reg = _registry_for(registry, results, rng)
    for result in results:
        _check_resolved(result, reg)
    held = sum(len(r) for r in results)
    assert held > 300, "the corpus no longer exercises a fat result"
    if registry == "empty":
        assert all(r.resolve(reg)[:2] == ([], {}) for r in results)


@pytest.mark.parametrize("shape", ["chained_intents", "native_set"])
def test_resolve_caches_nothing_on_shared_results(shape):
    """A cached result resolved against two registries gives two
    answers, and the first again afterwards: liveness is read from the
    registry it is shown, never remembered on the object."""
    rng = random.Random(7)
    results = _resolve_results(shape, rng)
    fat = max(results, key=len)
    reg_a = _registry_for("half", results, rng)
    reg_b = _registry_for("half", results, rng)
    assert set(reg_a) != set(reg_b)
    first = fat.resolve(reg_a)
    _check_resolved(fat, reg_b)
    second = fat.resolve(reg_b)
    assert [c.id for c, _s in first[0]] != [c.id for c, _s in second[0]]
    again = fat.resolve(reg_a)
    assert [(c, id(s)) for c, s in again[0]] == \
        [(c, id(s)) for c, s in first[0]]
    assert again[1:] == first[1:]
    del reg_a["not-in-the-table"]           # the registry is read live
    assert fat.resolve(reg_a)[2:] == first[2:]
    gone = first[0][0][0].id
    del reg_a[gone]
    assert gone not in [c.id for c, _s in fat.resolve(reg_a)[0]]


def test_resolve_rejects_a_non_dict_registry():
    mod = _native_mod()
    with pytest.raises(TypeError):
        mod.SubscriberSet().resolve(["c1"])


# --------------------------------------------------------------------
# Dual-width bit-planes (ADR 010): packed 16-bit plane compare for
# groups whose signatures admit an injective 16-bit fold, 32-bit planes
# for the rest — exact parity required in every mix.
# --------------------------------------------------------------------


def _engineered_width_corpus(monkeypatch, max_rows16=8):
    """Corpus with BOTH plane widths: one '#'-shape with more unique
    rows than the (patched) eligibility bound stays 32-bit, a smaller
    shape goes 16-bit."""
    import maxmq_tpu.matching.sig as sigmod
    monkeypatch.setattr(sigmod, "W16_MAX_GROUP_ROWS", max_rows16)
    idx = TopicIndex()
    for i in range(30):                        # shape (#, depth 1): 30 rows
        idx.subscribe(f"w{i}", Subscription(filter=f"r{i}/#", qos=1))
    for i in range(5):                         # shape (#, depth 2): 5 rows
        idx.subscribe(f"n{i}", Subscription(filter=f"x/y{i}/#", qos=2))
    idx.subscribe("sh", Subscription(filter="$share/g/x/y0/#"))
    idx.subscribe("pl", Subscription(filter="x/+/q"))     # host-probed
    idx.subscribe("ex", Subscription(filter="r0/exact"))  # host-probed
    return idx


def test_mixed_width_compile_layout(monkeypatch):
    """Eligibility splits per group; 16-bit groups are laid out LAST
    (contiguous word regions per width); folds are injective and avoid
    the 0xFFFF pad poison."""
    idx = _engineered_width_corpus(monkeypatch)
    tables = compile_sig(idx)
    w16 = tables.group_w16
    assert w16.any() and (~w16).any(), "need both widths"
    # 32-bit groups strictly precede 16-bit groups
    first16 = int(np.argmax(w16))
    assert w16[first16:].all() and not w16[:first16].any()
    from maxmq_tpu.matching.sig import _fold16
    for gi, g in enumerate(tables.groups):
        rows = np.asarray(g.rows)
        sig16 = tables.row_sig16[rows]
        if w16[gi]:
            assert tables.fold_mult[gi] % 2 == 1
            assert (sig16 != 0xFFFF).all()
            assert len(np.unique(sig16)) == len(sig16), "fold not injective"
            # the stored fold IS the multiply-shift of the row sigs
            np.testing.assert_array_equal(
                sig16, _fold16(tables.row_sig[rows], tables.fold_mult[gi]))
        else:
            assert tables.fold_mult[gi] == 0
    # pad rows carry the 16-bit poison
    pad = np.ones(len(tables.row_sig16), dtype=bool)
    for g in tables.groups:
        pad[np.asarray(g.rows)] = False
    assert (tables.row_sig16[pad] == 0xFFFF).all()


def test_mixed_width_parity_and_equality(monkeypatch):
    """The mixed-width kernel must be bit-exact with the 32-bit-forced
    kernel AND the CPU trie at the decoded-result boundary, on a corpus
    where some groups are 16-bit-eligible and some are not (16-bit fold
    collisions only add host-verified candidates or overflow to the
    exact trie fallback — results never change)."""
    idx = _engineered_width_corpus(monkeypatch)
    rng = random.Random(4)
    topics = ([f"r{i}/t/{j}" for i in range(30) for j in (0, 1)]
              + [f"x/y{i}/deep/er" for i in range(5)]
              + ["x/zz/q", "r0/exact", "$SYS/x", "x/y0", "none/here"]
              + ["/".join(rng.choice(["r0", "x", "y0", "q", "zz"])
                          for _ in range(rng.randint(1, 5)))
                 for _ in range(40)])
    results = {}
    for kw in ("auto", "32"):
        for use_pallas in ("auto", False):
            engine = SigEngine(idx, use_pallas=use_pallas,
                               kernel_width=kw)
            got = engine.subscribers_fixed_batch(topics)
            for topic, result in zip(topics, got):
                want = idx.subscribers(topic)
                assert normalize(result) == normalize(want), (
                    f"[width={kw}/pallas={use_pallas}] {topic!r}")
            if use_pallas == "auto":
                assert engine.pallas_active
                plan = engine.kernel_plan
                assert plan is not None
                if kw == "auto":
                    assert plan["groups16"] and plan["groups32"]
                else:
                    assert plan["groups16"] == 0
                results[kw] = [normalize(r) for r in got]
    assert results["auto"] == results["32"]


def test_mixed_width_all_paths_parity(monkeypatch):
    """word/compact/fixed paths stay exact on a dual-width table set
    (word + compact run the unchanged 32-bit XLA body over the
    REORDERED row layout — the reorder itself must be seamless)."""
    idx = _engineered_width_corpus(monkeypatch)
    check_parity(idx, [f"r{i}/a" for i in range(8)]
                 + ["x/y0/b/c", "x/y3", "x/q/q", "$share/x", "r5"])


def test_plan_force_width32(monkeypatch):
    """force_width32 plans the SAME tables all-32: word totals are
    conserved and the predicted plane passes drop in the mixed plan."""
    from maxmq_tpu.matching import sig_pallas

    idx = _engineered_width_corpus(monkeypatch)
    tables = compile_sig(idx)
    mixed = sig_pallas.plan(tables)
    forced = sig_pallas.plan(tables, force_width32=True)
    assert mixed is not None and forced is not None
    assert mixed["n_words16"] > 0 and forced["n_words16"] == 0
    assert (mixed["n_words32"] + mixed["n_words16"]
            == forced["n_words32"])
    assert forced["groups16"] == 0
    # per padded column the packed compare halves the pass count
    assert (mixed["plane_passes_per_topic"]
            < 32 * (mixed["n_chunks32"] + mixed["n_chunks16"])
            * mixed["chunk"])


def test_kernel_width_arg_validated():
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b"))
    with pytest.raises(ValueError):
        SigEngine(idx, kernel_width="16")


def test_randomized_mixed_width_churn_parity(monkeypatch):
    """Randomized corpora + churn under a small eligibility bound so
    recompiles keep flipping groups between widths — every match must
    stay exact through rotations."""
    import maxmq_tpu.matching.sig as sigmod
    monkeypatch.setattr(sigmod, "W16_MAX_GROUP_ROWS", 6)
    rng = random.Random(77)
    filters, topics = rand_corpus(rng, 200, 30)
    idx = TopicIndex()
    from maxmq_tpu.matching.topics import valid_filter
    live = []
    for i, f in enumerate(filters[:120]):
        if not valid_filter(f):
            continue
        cid = f"cl-{i % 30}"
        idx.subscribe(cid, Subscription(filter=f, qos=i % 3))
        live.append((cid, f))
    engine = SigEngine(idx, auto_refresh=False)
    pool = [f for f in filters[120:] if valid_filter(f)]
    for step in range(30):
        if rng.random() < 0.5 and pool:
            cid = f"cl-{rng.randrange(30)}"
            f = pool.pop()
            idx.subscribe(cid, Subscription(filter=f, qos=1))
            live.append((cid, f))
        elif live:
            cid, f = live.pop(rng.randrange(len(live)))
            idx.unsubscribe(cid, f)
        if rng.random() < 0.3:
            engine.refresh(force=True)
        batch = [rng.choice(topics) for _ in range(5)]
        got = engine.subscribers_fixed_batch(batch)
        for topic, result in zip(batch, got):
            want = idx.subscribers(topic)
            assert normalize(result) == normalize(want), (step, topic)


def test_sig_dual_width_kernel_raw_outputs(monkeypatch):
    """Dual-width signature kernels at the RAW output level: on one
    compiled table set, the mixed-width program's per-topic candidate
    counts must be a superset of the 32-bit-forced program's wherever
    neither overflows (a 16-bit fold can only add host-verified false
    candidates or overflow — never drop a true match), and the row
    slots must agree exactly on topics where the counts agree."""
    import maxmq_tpu.matching.sig as sigmod
    from maxmq_tpu.matching import sig_pallas
    from maxmq_tpu.matching.sig import prepare_batch

    monkeypatch.setattr(sigmod, "W16_MAX_GROUP_ROWS", 8)
    idx = TopicIndex()
    for i in range(30):
        idx.subscribe(f"w{i}", Subscription(filter=f"k{i}/#", qos=1))
    for i in range(5):
        idx.subscribe(f"n{i}", Subscription(filter=f"m/z{i}/#", qos=2))
    engine = SigEngine(idx, use_pallas=True, fixed_max_rows=7)
    assert engine.pallas_active
    tables, consts = engine._state[0], engine._state[1]
    assert tables.group_w16.any() and (~tables.group_w16).any()

    rng = random.Random(6)
    topics = ([f"k{i}/t" for i in range(30)]
              + [f"m/z{i}/d/e" for i in range(5)]
              + ["m/q", "$SYS/x", "none"]
              + ["/".join(rng.choice(["k0", "m", "z0", "q"])
                          for _ in range(rng.randint(1, 4)))
                 for _ in range(20)])
    toks8, lens_enc, _ = prepare_batch(tables, topics)

    outs = {}
    for label, force in (("mixed", False), ("force32", True)):
        kplan = sig_pallas.plan(tables, force_width32=force)
        assert kplan is not None
        fn, fmt = sig_pallas.build_fixed_fn(tables, consts, kplan,
                                            max_rows=7)
        assert fmt["kind"] == "stream"
        cnt, stream = fn(toks8, lens_enc)
        outs[label] = (np.asarray(cnt), np.asarray(stream))

    m_cnt, m_stream = outs["mixed"]
    f_cnt, f_stream = outs["force32"]
    both = (m_cnt != 0xFF) & (f_cnt != 0xFF)
    assert both.any()
    assert (m_cnt[both].astype(int) >= f_cnt[both].astype(int)).all()
    # where the counts agree, the row slots must be identical (stream
    # is topic-ordered; walk both with per-arm offsets)
    mo = fo = 0
    checked = 0
    for i in range(len(topics)):
        mc = int(m_cnt[i]) if m_cnt[i] != 0xFF else 0
        fc = int(f_cnt[i]) if f_cnt[i] != 0xFF else 0
        if m_cnt[i] != 0xFF and f_cnt[i] != 0xFF and mc == fc:
            assert np.array_equal(m_stream[mo:mo + mc],
                                  f_stream[fo:fo + fc]), topics[i]
            checked += 1
        mo += mc
        fo += fc
    assert checked, "no comparable topics"
