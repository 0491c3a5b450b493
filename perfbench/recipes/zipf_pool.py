"""Topics drawn Zipf(s) from a fixed pool of corpus-shaped topics: the
repeat-heavy stream a topic cache is there for."""

from __future__ import annotations

import bisect
import itertools
import random

from generators import corpus_topic


def zipf_pool(seed: int, hits: list, pool: int = 10000, s: float = 1.0,
              **_params):
    rng = random.Random(seed + 11)
    topics = [corpus_topic(rng) for _ in range(pool)]
    cum = list(itertools.accumulate(1.0 / (k + 1) ** s for k in range(pool)))
    top = cum[-1]
    return lambda r: topics[bisect.bisect_left(cum, r.random() * top)]
