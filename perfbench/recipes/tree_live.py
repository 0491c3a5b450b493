"""The live half of ``tree-100k``: the dashboards, rule engines and
historians that watch nested slices of ``tree_table``'s tree."""

from __future__ import annotations

import random

from recipes.tree_table import DEPTH, LEAVES, LETTERS, WIDTH, leaf

ANY = "+"
# deliveries a message, whatever the seed: the sessions of each kind
# times the leaves the kind's widest slice covers, over all the leaves
# (every other filter of a session lies inside its widest slice)
KINDS = {"site": (20, WIDTH ** 4), "area": (100, WIDTH ** 3),
         "kind": (100, WIDTH ** 3), "point": (30, WIDTH ** 4),
         "cross": (50, WIDTH ** 3), "cell": (100, WIDTH ** 2)}
RECEIVERS = sum(n * covered for n, covered in KINDS.values()) / LEAVES


def _filter(*levels, rest: bool = False) -> str:
    """Levels given as digits (named by their depth) or ``+``; ``rest``
    ends the filter in ``#``."""
    names = [ANY if d == ANY else f"{LETTERS[at]}{d}"
             for at, d in enumerate(levels)]
    return "/".join(names + ["#"] if rest else names)


def grants(n: int) -> tuple[int, int]:
    """(QoS of a session's widest slice, QoS of the slices inside it)
    for the ``n``-th session of its kind. One in four is a historian or
    a rule engine and subscribes at QoS 1 throughout; the others are
    dashboards at QoS 0, but for one in eight that holds filters at
    both: its alarms (the inner slices) at QoS 1 over a QoS 0 overview,
    or, every other time, a QoS 1 overview over QoS 0 details."""
    if n % 4 == 0:
        return 1, 1
    if n % 8 == 1:
        return (0, 1) if n % 16 == 1 else (1, 0)
    return 0, 0


def tree_live(seed: int, scale: float = 1.0, **_params
              ) -> tuple[dict, dict, list]:
    """(client id -> [(filter, qos)], no share groups, the exact leaves
    the plan names). At ``scale`` 1: 400 persistent sessions holding
    1,470 filters that nest: a session's first filter is its widest
    slice and the others lie inside it, so several of one session's
    filters meet on one topic and the session still gets one copy, at
    the highest QoS among them (``grants`` says who holds which QoS).

    - 20 site watchers, two a site ``i``: ``a<i>/#``, and inside it
      ``a<i>/b<j>/#`` for two seeded ``j`` and ``a<i>/+/+/+/e<m>`` for
      one seeded ``m``;
    - 100 area watchers, one an area: ``a<i>/b<j>/#``, and inside it
      ``a<i>/b<j>/c<k>/+/+`` for two seeded ``k`` and
      ``a<i>/b<j>/+/+/e<m>``;
    - 100 line-kind watchers, one a pair (i, k): ``a<i>/+/c<k>/#``, and
      inside it ``a<i>/+/c<k>/d<l>/#`` for two seeded ``l`` and one
      exact leaf;
    - 30 point-kind watchers, three a point name ``m``:
      ``+/+/+/+/e<m>``, and inside it ``+/b<j>/+/+/e<m>`` for two
      seeded ``j``;
    - 50 cross watchers on distinct seeded (j, l): ``+/b<j>/+/d<l>/#``
      and inside it one ``+/b<j>/c<k>/d<l>/+``;
    - 100 cell watchers on distinct seeded cells: the cell's line
      ``a<i>/b<j>/c<k>/#``, and inside it ``a<i>/b<j>/c<k>/d<l>/+`` and
      two exact leaves of the cell.

    A uniform leaf reaches ``RECEIVERS`` = 7.6 sessions (2 + 1 + 1 + 3
    + 0.5 + 0.1) whatever the seed, because the seed moves only what
    lies inside a widest slice. ``scale`` cuts every kind's count in
    proportion (the rehearsal's tenth: 40 sessions, 147 filters) and
    leaves the shapes."""
    rng = random.Random(seed + 41)
    digits = range(WIDTH)

    def n_of(kind: str) -> int:
        return max(1, round(KINDS[kind][0] * scale))

    def two() -> list[int]:
        return rng.sample(digits, 2)

    plan: dict[str, list] = {}
    hits: list[str] = []

    def session(cid: str, n: int, widest: str, inner: list) -> None:
        outer_qos, inner_qos = grants(n)
        plan[cid] = [(widest, outer_qos)] + [(f, inner_qos) for f in inner]

    def exact(*known) -> str:
        """One seeded leaf under the levels given."""
        at = list(known) + [rng.randrange(WIDTH)
                            for _ in range(DEPTH - len(known))]
        hits.append("/".join(leaf(at)))
        return hits[-1]

    for n in range(n_of("site")):
        i = n // 2
        session(f"tree-site-{i}-{n % 2}", n, _filter(i, rest=True),
                [_filter(i, j, rest=True) for j in two()]
                + [_filter(i, ANY, ANY, ANY, rng.randrange(WIDTH))])
    for n in range(n_of("area")):
        i, j = divmod(n, WIDTH)
        session(f"tree-area-{i}-{j}", n, _filter(i, j, rest=True),
                [_filter(i, j, k, ANY, ANY) for k in two()]
                + [_filter(i, j, ANY, ANY, rng.randrange(WIDTH))])
    for n in range(n_of("kind")):
        i, k = divmod(n, WIDTH)
        session(f"tree-kind-{i}-{k}", n, _filter(i, ANY, k, rest=True),
                [_filter(i, ANY, k, d, rest=True) for d in two()]
                + [exact(i, rng.randrange(WIDTH), k)])
    for n in range(n_of("point")):
        m = n // 3
        session(f"tree-point-{m}-{n % 3}", n, _filter(ANY, ANY, ANY, ANY, m),
                [_filter(ANY, j, ANY, ANY, m) for j in two()])
    for n, jl in enumerate(rng.sample(range(WIDTH ** 2), n_of("cross"))):
        j, d = divmod(jl, WIDTH)
        session(f"tree-cross-{j}-{d}", n, _filter(ANY, j, ANY, d, rest=True),
                [_filter(ANY, j, rng.randrange(WIDTH), d, ANY)])
    for n, cell in enumerate(rng.sample(range(WIDTH ** 4), n_of("cell"))):
        at = [cell // WIDTH ** p % WIDTH for p in (3, 2, 1, 0)]
        session("tree-cell-" + "-".join(map(str, at)), n,
                _filter(*at[:3], rest=True),
                [_filter(*at, ANY)] + [exact(*at, m) for m in two()])
    return plan, {}, hits
