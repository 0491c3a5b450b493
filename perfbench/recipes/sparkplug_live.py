"""The live half of the Sparkplug B plant of ``sparkplug_table``: the
connected edge nodes and the host applications."""

from __future__ import annotations

from recipes.sparkplug_table import NAMESPACE, Plant

AREA_APPS = 10


def sparkplug_live(seed: int, **plant) -> tuple[dict, dict, list]:
    """(client id -> [(filter, qos)], no share groups, the NCMD and DCMD
    topics of the live nodes). Every live edge node holds its three
    filters; ``sp-host-primary`` the whole namespace and its own STATE
    topic, ``sp-host-historian`` the whole namespace, and each of ten
    ``sp-host-area<k>`` every tenth group, so each group has one. All
    granted QoS 1 (the data they carry is published at QoS 0)."""
    p = Plant(seed, **plant)
    subs: dict[str, list] = {}
    hits: list[str] = []
    for group, node in p.live:
        subs[f"sp-edge-{group}-{node}"] = [
            (f, 1) for f in p.node_filters(group, node)]
        hits.append(f"{NAMESPACE}/{group}/NCMD/{node}")
        hits += [f"{NAMESPACE}/{group}/DCMD/{node}/{d}" for d in p.devices]
    subs["sp-host-primary"] = [(f"{NAMESPACE}/#", 1), (p.state_topic, 1)]
    subs["sp-host-historian"] = [(f"{NAMESPACE}/#", 1)]
    for k in range(AREA_APPS):
        subs[f"sp-host-area{k}"] = [(f"{NAMESPACE}/{g}/#", 1)
                                    for g in p.groups[k::AREA_APPS]]
    return subs, {}, hits
