"""What the matcher tests share: the comparable form of a match result,
the randomized filter/topic corpus, and the base of their fake engines."""

from __future__ import annotations


def normalize(ss):
    """Comparable form of a SubscriberSet."""
    subs = {cid: (s.qos, tuple(sorted(s.identifiers.items())))
            for cid, s in ss.subscriptions.items()}
    shared = {k: tuple(sorted(v)) for k, v in ss.shared.items()}
    return subs, shared


def as_set(result):
    """A match result as a SubscriberSet: an engine with ``emit_intents``
    on answers with fan-out-ready intents where its native decode served,
    and with sets where the trie did (ADR 007)."""
    to_set = getattr(result, "to_set", None)
    return to_set() if to_set is not None else result


def rand_corpus(rng, n_filters, n_clients, depth=5, alphabet=8):
    tokens = [f"t{i}" for i in range(alphabet)]
    filters = []
    for _ in range(n_filters):
        nlev = rng.randint(1, depth)
        levels = []
        for li in range(nlev):
            r = rng.random()
            if r < 0.15:
                levels.append("+")
            elif r < 0.22 and li == nlev - 1:
                levels.append("#")
            elif r < 0.25:
                levels.append("")  # empty level
            else:
                levels.append(rng.choice(tokens))
        f = "/".join(levels)
        if rng.random() < 0.1:
            f = f"$share/g{rng.randint(0, 2)}/{f}"
        filters.append(f)
    topics = []
    for _ in range(n_filters):
        nlev = rng.randint(1, depth + 1)
        levels = [rng.choice(tokens + [""]) if rng.random() > 0.05
                  else f"unseen{rng.randint(0, 9)}" for _ in range(nlev)]
        t = "/".join(levels)
        if rng.random() < 0.08:
            t = "$" + t
        topics.append(t)
    return filters, topics


class EngineStub:
    """The base of a test's fake device engine: the side of the contract
    ``sig.OverlayedEngine`` states that a fake has nothing to say about
    (tables, warm-up, counters), so that it defines ``index`` and
    ``subscribers_batch`` and whatever else its test is about. The
    batcher and the broker call these plainly, as on a real engine."""

    auto_refresh = True
    compiling = False
    matches = fallbacks = host_matches = 0
    tracer = None

    def subscribers(self, topic):
        return self.subscribers_batch([topic])[0]

    def subscribers_host_batch(self, topics):
        return self.subscribers_batch(topics)

    def refresh(self, force=False):
        return False

    def refresh_soon(self):
        pass

    def rewarm(self):
        pass

    def prewarm_decode_bases(self, chunk=2048):
        return 0

    def close(self, timeout=30.0):
        pass
