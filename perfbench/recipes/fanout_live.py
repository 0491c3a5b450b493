"""The live population of ``fleet-fanout-1k``: the subscribers of the
Open MQTT Benchmark Suite's ``fanout-5-1000-5-250K`` (5 publishers, 5
topics, 1,000 subscribers on all five, QoS 1)."""

from __future__ import annotations

NAMESPACE = "fleet/broadcast"


def fanout_live(seed: int, subscribers: int = 1000, topics: int = 5,
                **_params) -> tuple[dict, dict, list]:
    """(client id -> [(filter, qos)], no share groups, the broadcast
    topics). Session ``bc-dev-<i>`` holds one exact filter a topic,
    ``fleet/broadcast/cmd-<k>``, at QoS 1. The level names lie outside
    the table's 96 (``generators.ALPHABET``) and a corpus filter keeps
    at least one literal level, so no stored filter matches a broadcast
    topic; every session holds the same five filters whatever the seed,
    as the source has it."""
    hits = [f"{NAMESPACE}/cmd-{k}" for k in range(topics)]
    plan = {f"bc-dev-{i}": [(t, 1) for t in hits]
            for i in range(subscribers)}
    return plan, {}, hits
