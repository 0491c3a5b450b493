"""What a run is made of, all of it from ``--seed``: the stored table,
the live population, and the topics of the traffic.

``corpus`` and ``corpus_topics`` are copies of ``bench.build_corpus`` and
its topic generator; ``live_plan`` is a copy of ``chip_smoke.live_plan``
(the originals stay where they are: PERF.md, Open questions). A recipe is
found by name: here first, then as ``perfbench/recipes/<name>.py`` with a
function of the same name, so a new mix that needs a new recipe adds a
file and edits none.
"""

from __future__ import annotations

import importlib
import random

ALPHABET = [f"{c}{i}" for c in "abcdefgh" for i in range(12)]


def corpus(n_subs: int, seed: int, share_frac: float = 0.1) -> list[str]:
    """``n_subs`` filters: depth 3-8 over 96 level names, 30% with one
    or two ``+``, 15% cut to a trailing ``#``, ``share_frac`` of all
    under ``$share/g0..7/``."""
    rng = random.Random(seed)
    filters = []
    for _ in range(n_subs):
        depth = rng.randint(3, 8)
        levels = [rng.choice(ALPHABET) for _ in range(depth)]
        r = rng.random()
        if r < 0.3:
            for _ in range(rng.randint(1, 2)):
                levels[rng.randrange(depth)] = "+"
        elif r < 0.45:
            levels = levels[: rng.randint(1, depth)] + ["#"]
        f = "/".join(levels)
        if share_frac and rng.random() < share_frac:
            f = f"$share/g{rng.randint(0, 7)}/{f}"
        filters.append(f)
    return filters


def corpus_topic(rng: random.Random) -> str:
    return "/".join(rng.choice(ALPHABET) for _ in range(rng.randint(3, 8)))


# -- the live population ---------------------------------------------------


def live_plan(seed: int, **_params) -> tuple[dict, dict, list]:
    """(subscriber id -> [(filter, qos)], share group -> member ids,
    topics that hit them): 64 subscribers, 160 filters. Plain '#', '+'
    and exact filters in the corpus's own namespace and in a ``live/``
    one, and four ``$share`` groups of four whose members hold no plain
    filter on the group's topics, so "once per group" can be checked
    member by member."""
    rng = random.Random(seed + 1)
    r2 = random.Random(seed + 2)
    exact = [corpus_topic(r2) for _ in range(16)]
    subs: dict[str, list] = {}
    hits: list[str] = list(exact)
    for i in range(48):
        cid, a, b = f"live-s{i}", ALPHABET[i], ALPHABET[i + 48]
        if i < 16:
            subs[cid] = [(f"{a}/#", i % 2), (f"live/d{i}/#", 1)]
            hits += [f"live/d{i}/state", f"live/d{i}/a/b"]
        elif i < 32:
            subs[cid] = [(f"+/{a}/+", i % 2), (f"{a}/+/{b}/#", 0),
                         ("live/+/cmd", 1), (f"live/+/cmd/{i}", 0)]
            hits += [f"{ALPHABET[i - 16]}/{a}/{b}", f"{a}/x/{b}/y",
                     f"live/d{i}/cmd", f"live/d{i}/cmd/{i}"]
        else:
            subs[cid] = [(exact[i - 32], i % 2), (f"live/d{i}/state", 1)]
            hits.append(f"live/d{i}/state")
    group_filters = ["live/+/telemetry", "live/+/telemetry",
                     f"{ALPHABET[90]}/#", f"+/+/{ALPHABET[91]}"]
    groups: dict[str, list] = {}
    for g, filt in enumerate(group_filters):
        members = [f"live-s{48 + 4 * g + m}" for m in range(4)]
        groups[f"live{g}"] = members
        for m, cid in enumerate(members):
            subs[cid] = [(f"$share/live{g}/{filt}", (g + m) % 2),
                         (f"live/inbox/{cid}", 0)]
            hits.append(f"live/inbox/{cid}")
    hits += [f"live/d{i}/telemetry" for i in range(16)]
    hits += [f"{ALPHABET[90]}/{rng.choice(ALPHABET)}/{rng.choice(ALPHABET)}"
             for _ in range(8)]
    hits += [f"{rng.choice(ALPHABET)}/{rng.choice(ALPHABET)}/{ALPHABET[91]}"
             for _ in range(8)]
    return subs, groups, hits


# -- topic recipes: make(seed, hits, **params) -> draw(rng) -> topic -------


def corpus_topics(seed: int, hits: list, **_params):
    """A fresh topic of the corpus's shape every time: with 96^3 and
    more of them no topic repeats, so no topic cache can answer."""
    return corpus_topic


def live_hits(seed: int, hits: list, **_params):
    """One of the topics the live subscribers' filters were made for."""
    return lambda rng: rng.choice(hits)


def find(name: str):
    """The generator of that name: one of this module's, or
    ``perfbench/recipes/<name>.py``'s function ``<name>``."""
    here = globals().get(name)
    if callable(here) and not name.startswith("_"):
        return here
    return getattr(importlib.import_module(f"recipes.{name}"), name)


def topic_source(traffic: dict, seed: int, hits: list):
    """draw(rng) -> topic, mixing the traffic file's recipes by share."""
    parts = [(t["share"], find(t["recipe"])(seed, hits, **t.get("args", {})))
             for t in traffic["topics"]]
    total = sum(s for s, _ in parts)
    edges, acc = [], 0.0
    for share, draw in parts:
        acc += share / total
        edges.append((acc, draw))
    last = edges[-1][1]

    def draw(rng: random.Random) -> str:
        r = rng.random()
        for edge, fn in edges:
            if r < edge:
                return fn(rng)
        return last(rng)
    return draw
