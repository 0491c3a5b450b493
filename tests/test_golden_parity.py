"""The MQTT 4.7 golden corpora, once, on every path that answers a topic.

Each case builds a TopicIndex from a hand-written corpus, asks one answer
path for the subscribers of every topic, and compares with the CPU
reference trie (``TopicIndex.subscribers``) through ``normalize``. The
paths are the ones a served publish can take: the device programs of
``SigEngine`` (word and compact forms; the fixed-slot form the batcher
calls, as sets and as intents), its device-free host probe (what answers
in the benchmark's cells), ``ShardedSigEngine`` on a 1x4 mesh, and the
served entry itself, ``MicroBatcher.enqueue`` with the bypass on (topic
cache, trie walk, host probe)."""

from __future__ import annotations

import asyncio
from dataclasses import dataclass, field

import pytest

from maxmq_tpu.matching import TopicIndex
from maxmq_tpu.matching.batcher import MicroBatcher
from maxmq_tpu.matching.sig import SigEngine
from maxmq_tpu.parallel.sharded import ShardedSigEngine, make_mesh
from maxmq_tpu.protocol import Subscription

from matching_helpers import as_set, normalize


@pytest.fixture(autouse=True)
def _always_device_path(monkeypatch):
    """The ADR-008 small-corpus router must not serve these few filters
    from the trie: parity would pass without the path under test."""
    monkeypatch.setattr(SigEngine, "ROUTE_SUBS_MAX", -1)


def sub(cid, filt, **kw):
    return ("sub", cid, Subscription(filter=filt, **kw))


def unsub(cid, filt):
    return ("unsub", cid, filt)


@dataclass
class Corpus:
    """Steps of (mutations, topics): the first step's mutations build the
    index before the engine exists, a later step's change it under a live
    engine, and after each step every topic is asked and compared."""

    steps: list
    engine_kw: dict = field(default_factory=dict)   # SigEngine's only
    # held after the last step, given the SigEngine (not the mesh engine,
    # whose tables are per shard)
    after_sig: object = None


def _one_group_entry(engine):
    # one row bit for the whole $share group, its members in the
    # candidate map: no row per member
    (entry,) = engine.tables.entries
    assert entry.shared and len(entry.candidates) == 5


def _fell_back(engine):
    assert engine.fallbacks > 0


DEEP = "a/" + "/".join(str(i) for i in range(80))

CORPORA = {
    "basics": Corpus([(
        [sub("c1", "a/b/c", qos=1), sub("c2", "a/+/c", qos=2),
         sub("c3", "a/#"), sub("c4", "#"), sub("c5", "+")],
        ["a/b/c", "a/x/c", "a", "a/b", "x", "x/y", "a/b/c/d", "$SYS/x",
         "$SYS"])]),
    "hash_parent_and_dollar": Corpus([(
        [sub("c1", "sport/tennis/#"), sub("c2", "$SYS/#"),
         sub("c3", "$SYS/+/x"), sub("c4", "+/tennis/+")],
        ["sport/tennis", "sport/tennis/p1", "sport", "$SYS/broker/x",
         "$SYS/broker", "$SYS", "a/tennis/b"])]),
    "empty_levels_and_unknown_tokens": Corpus([(
        [sub("c1", "/"), sub("c2", "//"), sub("c3", "+/"),
         sub("c4", "a//b")],
        ["/", "//", "a//b", "never-seen-token/x", "a/b", "never/", "/"])]),
    "shared_subscriptions": Corpus([(
        [sub("w1", "$share/g1/t/+"), sub("w2", "$share/g1/t/+"),
         sub("w3", "$share/g2/t/a"), sub("n1", "t/a", qos=1)],
        ["t/a", "t/b", "t", "x"])]),
    "overlap_merge": Corpus([(
        [sub("c1", "m/+", qos=0, identifier=3),
         sub("c1", "m/x", qos=2, identifier=9),
         sub("c1", "m/#", qos=1, identifier=4)],
        ["m/x", "m/y", "m"])]),
    "too_deep_topic_falls_back": Corpus(
        [([sub("c1", "a/#")], [DEEP, "a/b"])],
        engine_kw={"max_levels": 8}, after_sig=_fell_back),
    "incremental_refresh": Corpus([
        ([sub("c1", "a/b")], ["a/b"]),
        ([sub("c2", "a/+")], ["a/b", "a/c"]),
        ([unsub("c1", "a/b")], ["a/b"])]),
    "empty_index": Corpus([([], ["a/b", "$SYS/x", "/"])]),
    "hash_at_max_levels_boundary": Corpus(
        # '#' one level past max_levels still parent-matches the topic
        # that is exactly max_levels deep [MQTT-4.7.1.2]
        [([sub("c1", "l0/l1/l2/l3/#")],
          ["l0/l1/l2/l3", "l0/l1/l2/l3/l4", "l0/l1/l2"])],
        engine_kw={"max_levels": 4}),
    "shared_group_rows_deduplicated": Corpus(
        [([sub(f"w{i}", "$share/g1/t/+") for i in range(5)],
          ["t/a", "t", "t/a/b"])],
        after_sig=_one_group_entry),
    # fleet-fanin-500's ingest pool: one $share key with 500 candidates
    # (one row bit, a candidate map of 500) beside a plain subscriber
    "wide_share_group": Corpus(
        [([sub(f"ingest-{i}", "$share/ingest/fleet/telemetry/#", qos=1)
           for i in range(500)]
          + [sub("audit", "fleet/+/dev-7"),
             sub("ingest-3", "$share/other/fleet/telemetry/dev-7")],
          ["fleet/telemetry/dev-7", "fleet/telemetry/dev-49999",
           "fleet/telemetry", "fleet/broadcast/cmd-1", "fleet"])]),
}


def apply(index, mutations):
    for op, cid, arg in mutations:
        if op == "sub":
            index.subscribe(cid, arg)
        else:
            index.unsubscribe(cid, arg)


def run_steps(index, steps, make_answer):
    """Build the answer path over the first step's index, then hold it to
    the trie after every step. Returns what ``make_answer`` built."""
    apply(index, steps[0][0])
    built, answer = make_answer(index)
    for n, (mutations, topics) in enumerate(steps):
        if n:
            apply(index, mutations)
        got = answer(topics)
        assert len(got) == len(topics)
        for topic, result in zip(topics, got):
            assert normalize(as_set(result)) == normalize(
                index.subscribers(topic)), (
                f"step {n}: mismatch on topic {topic!r}")
    return built


def engine_path(*surfaces, intents=False, pallas=("auto",)):
    """A path over SigEngine: each named batch surface, on an engine per
    fixed-path device program asked for."""
    def run(corpus):
        for use_pallas in pallas:
            for surface in surfaces:
                def make(index):
                    engine = SigEngine(index, use_pallas=use_pallas,
                                       **corpus.engine_kw)
                    engine.emit_intents = intents
                    return engine, getattr(engine, surface)
                engine = run_steps(TopicIndex(), corpus.steps, make)
                if corpus.after_sig is not None:
                    corpus.after_sig(engine)
    return run


def sharded_path(corpus):
    def make(index):
        engine = ShardedSigEngine(index, mesh=make_mesh(shape=(1, 4)))
        return engine, engine.subscribers_batch
    run_steps(TopicIndex(), corpus.steps, make)


def enqueue_path(corpus):
    """``await MicroBatcher(SigEngine).enqueue(topic)`` with a device
    round trip on record that every small batch undercuts, so the bypass
    answers: from the trie, then from the host probe; and each batch asked
    twice, so the topic cache answers too."""
    loop = asyncio.new_event_loop()

    async def ask(batcher, topics):
        return await asyncio.gather(*[batcher.enqueue(t) for t in topics])

    def served_by(trie_cost):
        def make(index):
            engine = SigEngine(index, **corpus.engine_kw)
            batcher = MicroBatcher(engine, window_us=0)
            batcher._device_rtt, batcher._rtt_samples = 10.0, 2
            batcher._trie_cost = trie_cost

            def answer(topics):
                first = loop.run_until_complete(ask(batcher, topics))
                hits = batcher.cache_hits
                again = loop.run_until_complete(ask(batcher, topics))
                assert batcher.cache_hits - hits == len(topics)
                assert ([normalize(as_set(r)) for r in again]
                        == [normalize(as_set(r)) for r in first])
                return first
            return batcher, answer
        batcher = run_steps(TopicIndex(), corpus.steps, make)
        try:
            assert 0 < batcher.bypasses == batcher.batched_topics
            return batcher.engine.host_matches
        finally:
            loop.run_until_complete(batcher.close())

    try:
        assert served_by(trie_cost=1e-9) == 0       # the trie walk
        # the host probe (which counts no topic it left to the trie)
        assert served_by(trie_cost=1.0) > 0 or not corpus.steps[0][0]
    finally:
        loop.close()


PATHS = {
    # the word and compact output forms of the device program
    "batch": engine_path("subscribers_batch", "subscribers_compact_batch"),
    # what the batcher calls: both fixed-path device programs, the fused
    # Pallas kernel (auto) and the XLA body (False)
    "fixed": engine_path("subscribers_fixed_batch", pallas=("auto", False)),
    "intents": engine_path("subscribers_fixed_batch", intents=True),
    # what answers in the benchmark's cells
    "host": engine_path("subscribers_host_batch"),
    "sharded": sharded_path,
    "enqueue": enqueue_path,
}


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("corpus", list(CORPORA))
def test_golden(corpus, path):
    PATHS[path](CORPORA[corpus])
