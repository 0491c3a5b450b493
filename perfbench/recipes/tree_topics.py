"""The traffic of ``tree-100k``: every point of the tree reporting."""

from __future__ import annotations

from recipes.tree_table import draw_leaf


def tree_topics(seed: int, hits: list, **_params):
    """A leaf ``a<i>/b<j>/c<k>/d<l>/e<m>`` drawn uniformly from the
    100,000: twelve times the topic cache's 8,192 entries, no hot head.
    Nothing is aimed: the watchers' filters decide who receives."""
    return lambda rng: "/".join(draw_leaf(rng))
