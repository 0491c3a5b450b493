"""A Sparkplug B plant (Eclipse Sparkplug 3.0.0, ch. 4) and the stored
half of its subscription table.

The namespace is ``spBv1.0/<group_id>/<message_type>/<edge_node_id>
[/<device_id>]``. Group, edge-node and host ids are drawn from the seed
(a table is a constant of the compiled programs, so a new seed is a new
compile, as with the fleet's corpus); a node's devices are ``d00``,
``d01``, ... under it. ``sparkplug_live`` and ``sparkplug_topics`` build
the same plant from the same seed: which nodes are live, and every name,
are decided here alone.
"""

from __future__ import annotations

import random

NAMESPACE = "spBv1.0"
AREAS = ("press", "weld", "paint", "trim", "body", "cast", "mill", "pack",
         "util", "test")
CELLS = ("line", "cell", "skid", "rtu", "gw", "plc")


class Plant:
    """``groups`` group ids, ``nodes`` edge-node ids under each, the
    primary host's id, and which ``live_nodes`` of the edge nodes are
    connected (the rest are stored subscriptions only)."""

    def __init__(self, seed: int, groups: int = 50, nodes: int = 200,
                 devices: int = 10, live_nodes: int = 256) -> None:
        rng = random.Random(seed + 21)
        self.groups = _distinct(rng, AREAS, groups, 4)
        self.nodes = [_distinct(rng, CELLS, nodes, 5) for _ in self.groups]
        self.devices = [f"d{k:02d}" for k in range(devices)]
        self.state_topic = (f"{NAMESPACE}/STATE/"
                            f"scada-{rng.randrange(16 ** 4):04x}")
        live = set(rng.sample(range(groups * nodes), live_nodes))
        self.live, self.stored = [], []     # (group id, edge-node id)
        for k in range(groups * nodes):
            g, n = divmod(k, nodes)
            (self.live if k in live else self.stored).append(
                (self.groups[g], self.nodes[g][n]))

    def node_filters(self, group: str, node: str) -> list[str]:
        """What every edge node subscribes to (spec ch. 5): its own
        commands, its devices' commands, the primary host's STATE."""
        return [f"{NAMESPACE}/{group}/NCMD/{node}/#",
                f"{NAMESPACE}/{group}/DCMD/{node}/#", self.state_topic]


def _distinct(rng: random.Random, words: tuple, n: int, digits: int) -> list:
    seen: dict = {}
    while len(seen) < n:
        seen[f"{rng.choice(words)}-{rng.randrange(16 ** digits):0{digits}x}"] \
            = None
    return list(seen)


def sparkplug_table(n_subs: int, seed: int, **plant) -> list[str]:
    """``n_subs`` stored filters: the three subscriptions of each edge
    node that is not live, node after node."""
    p = Plant(seed, **plant)
    filters = [f for node in p.stored[: -(-n_subs // 3)]
               for f in p.node_filters(*node)]
    if len(filters) < n_subs:
        raise ValueError(f"the plant stores {len(filters)} subscriptions, "
                         f"{n_subs} were asked for")
    return filters[:n_subs]
