"""A five-level topic tree and the stored half of its subscription
table: ``BASELINE.json`` ``configs[2]``, "mixed ``+``/``#`` wildcard
tree, 100K subs, deep a/b/c/d/e hierarchy".

The source bears out 100,000 subscriptions, ``+`` and ``#`` mixed, and
five levels named by their depth. The rest is assumed and listed in
``perfbench/configs/tree-100k.json``: ten names a level, so 100,000
leaf topics ``a<i>/b<j>/c<k>/d<l>/e<m>`` (twelve times the topic
cache's 8,192 entries), and the filter mix below. ``tree_live`` and
``tree_topics`` name their levels through ``leaf`` and ``LETTERS``: the
tree is decided here alone.
"""

from __future__ import annotations

import random

LETTERS = "abcde"
WIDTH = 10                      # names a level
DEPTH = len(LETTERS)
LEAVES = WIDTH ** DEPTH
# shares of the stored filters: exact, one '+', two '+', a trailing '#'
EXACT, ONE_PLUS, TWO_PLUS = 0.30, 0.25, 0.15
CUT_WEIGHTS = (1, 2, 3, 4)      # a '#' filter keeps 1, 2, 3 or 4 levels


def leaf(digits) -> list[str]:
    """The levels of leaf (i, j, k, l, m): ``a<i>``, ``b<j>``, ..."""
    return [f"{c}{d}" for c, d in zip(LETTERS, digits)]


def draw_leaf(rng: random.Random) -> list[str]:
    """A leaf drawn uniformly from the 100,000."""
    return leaf(rng.randrange(WIDTH) for _ in range(DEPTH))


def tree_table(n_subs: int, seed: int, **_params) -> list[str]:
    """``n_subs`` stored filters, each from a leaf drawn uniformly from
    the seed: 30% stay exact, 25% get one ``+`` and 15% two at seeded
    levels, 30% keep their first 1, 2, 3 or 4 levels (weights 1, 2, 3,
    4) and end in ``#``: a filter is 2 to 5 levels deep. No ``$share``.
    ``run.py`` stores filter ``i`` for client ``cl-<i>`` at QoS
    ``i % 3``, with no session record."""
    rng = random.Random(seed + 31)
    filters = []
    for _ in range(n_subs):
        levels = draw_leaf(rng)
        r = rng.random()
        if EXACT <= r < EXACT + ONE_PLUS + TWO_PLUS:
            for at in rng.sample(range(DEPTH),
                                 1 if r < EXACT + ONE_PLUS else 2):
                levels[at] = "+"
        elif r >= EXACT:
            keep = rng.choices(range(1, DEPTH), weights=CUT_WEIGHTS)[0]
            levels = levels[:keep] + ["#"]
        filters.append("/".join(levels))
    return filters
