"""The live population of ``fleet-fanin-500``: the consumers of the Open
MQTT Benchmark Suite's ``fanin-50K-500-50K-50K`` (50,000 publishers,
500 subscribers, 50,000 topics, QoS 1, the subscribers consuming through
a shared subscription)."""

from __future__ import annotations

NAMESPACE = "fleet/telemetry"
GROUP = "ingest"


def fanin_live(seed: int, subscribers: int = 500, devices: int = 50000,
               **_params) -> tuple[dict, dict, list]:
    """(client id -> [(filter, qos)], share group -> member ids, the
    device topics). Session ``ingest-<i>`` holds the one filter
    ``$share/ingest/fleet/telemetry/#`` at QoS 1; the topics are
    ``fleet/telemetry/dev-<i>``, one a device. No level is one of the
    table's 96 names (``generators.ALPHABET``) and a corpus filter keeps
    at least one literal level, so no stored filter matches a device
    topic: a message's receivers are one member of the group and nobody
    else. The population is the same whatever the seed, as the source
    has it."""
    members = [f"{GROUP}-{i}" for i in range(subscribers)]
    filt = f"$share/{GROUP}/{NAMESPACE}/#"
    plan = {cid: [(filt, 1)] for cid in members}
    hits = [f"{NAMESPACE}/dev-{i}" for i in range(devices)]
    return plan, {GROUP: members}, hits
