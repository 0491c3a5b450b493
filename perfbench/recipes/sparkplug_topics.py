"""A Sparkplug B plant's ordinary day: report by exception from every
device and edge node of ``sparkplug_table``'s plant, live or not, and a
few commands to the live ones."""

from __future__ import annotations

from recipes.sparkplug_table import NAMESPACE, Plant


def sparkplug_topics(seed: int, hits: list, ddata: float = 0.88,
                     ndata: float = 0.10, ncmd: float = 0.01, **plant):
    """DDATA with share ``ddata`` and NDATA with ``ndata``, uniform over
    all devices and edge nodes (a closed set with no hot head); NCMD with
    ``ncmd`` and DCMD with the rest, drawn from ``hits`` (the live
    nodes' command topics)."""
    p = Plant(seed, **plant)
    groups, nodes, devices = p.groups, p.nodes, p.devices
    ncmds = [t for t in hits if "/NCMD/" in t]
    dcmds = [t for t in hits if "/DCMD/" in t]
    data = ddata + ndata

    def draw(rng) -> str:
        r = rng.random()
        if r >= data:
            return rng.choice(ncmds if r < data + ncmd else dcmds)
        g = rng.randrange(len(groups))
        node = rng.choice(nodes[g])
        if r < ddata:
            return (f"{NAMESPACE}/{groups[g]}/DDATA/{node}/"
                    f"{rng.choice(devices)}")
        return f"{NAMESPACE}/{groups[g]}/NDATA/{node}"
    return draw
