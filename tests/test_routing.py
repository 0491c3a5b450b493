"""ADR 008 small-corpus auto-routing: tiny corpora serve from the CPU
trie; growing past ROUTE_SUBS_MAX flips to the device path — with
exact results either side of the flip."""


from maxmq_tpu.matching import TopicIndex
from maxmq_tpu.matching.sig import SigEngine
from maxmq_tpu.protocol import Subscription

from matching_helpers import normalize


def _as_set(r):
    to_set = getattr(r, "to_set", None)
    return to_set() if to_set is not None else r


def test_exact_corpus_above_threshold_takes_device_path():
    """A large exact-only corpus stays on the device path: with warmed
    buckets the device beats the trie even without wildcards (ADR 008);
    link-degraded regimes are the batcher bypass's job, not a static
    rule."""
    idx = TopicIndex()
    for i in range(2000):                  # > ROUTE_SUBS_MAX
        idx.subscribe(f"c{i}", Subscription(filter=f"ex/{i}/t", qos=1))
    eng = SigEngine(idx)
    got = eng.subscribers_fixed_batch(["ex/7/t", "ex/1999/t", "nope"])
    assert eng.trie_routed == 0
    assert "c7" in _as_set(got[0]).subscriptions
    assert "c1999" in _as_set(got[1]).subscriptions
    assert len(_as_set(got[2]).subscriptions) == 0


def test_tiny_mixed_corpus_routes_to_trie():
    idx = TopicIndex()
    for i in range(100):                   # <= ROUTE_SUBS_MAX
        idx.subscribe(f"c{i}", Subscription(filter=f"m/{i}/+", qos=0))
    eng = SigEngine(idx)
    got = eng.subscribers_fixed_batch(["m/3/x"])
    assert eng.trie_routed == 1
    assert "c3" in got[0].subscriptions


def test_crossing_threshold_flips_to_device():
    """Corpus growth past ROUTE_SUBS_MAX must engage the device path,
    with parity across the flip."""
    idx = TopicIndex()
    for i in range(SigEngine.ROUTE_SUBS_MAX - 10):
        idx.subscribe(f"e{i}", Subscription(filter=f"fl/{i}/t", qos=1))
    eng = SigEngine(idx)
    topics = ["fl/5/t", "fl/42/t"]
    eng.subscribers_fixed_batch(topics)
    assert eng.trie_routed == 2            # tiny: trie

    for i in range(40):                    # cross the threshold
        idx.subscribe(f"w{i}", Subscription(filter=f"fl/{i}/+", qos=0))
    eng.refresh(force=True)
    assert not eng._routes_to_trie()
    before = eng.trie_routed
    got2 = eng.subscribers_fixed_batch(topics)
    assert eng.trie_routed == before, "device path should have served"
    for t, r in zip(topics, got2):
        assert normalize(_as_set(r)) == normalize(idx.subscribers(t)), t


def test_route_small_off_restores_device_path():
    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b", qos=1))
    eng = SigEngine(idx)
    eng.route_small = False
    got = eng.subscribers_fixed_batch(["a/b"])
    assert eng.trie_routed == 0
    assert "c1" in _as_set(got[0]).subscriptions


async def test_batcher_honors_routing():
    """The batcher's pipelined split path must not force a device round
    trip for a corpus the router claims."""
    from maxmq_tpu.matching.batcher import MicroBatcher

    idx = TopicIndex()
    for i in range(50):
        idx.subscribe(f"c{i}", Subscription(filter=f"rb/{i}", qos=0))
    eng = SigEngine(idx)
    mb = MicroBatcher(eng, window_us=0, pipeline_depth=3)
    try:
        r = await mb.subscribers_async("rb/9")
        assert "c9" in r.subscriptions
        assert eng.trie_routed >= 1
    finally:
        await mb.close()
