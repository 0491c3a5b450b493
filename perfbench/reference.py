"""The plain reference: which live subscriptions a PUBLISH topic reaches.

MQTT 3.1.1 section 4.7 and MQTT 5 section 4.8.2, written straight down
and independent of every module of the program under test (its trie is
the supervisor's fallback path, so it is code under test too). It holds
the live subscribers' filters only (a few hundred), so a plain scan per
topic is cheap enough to check every message of a run.
"""

from __future__ import annotations


def split_share(filt: str) -> tuple[str, str]:
    """``$share/<group>/<filter>`` -> (group, filter); plain -> ("", filt)."""
    if filt.startswith("$share/"):
        _, group, rest = filt.split("/", 2)
        return group, rest
    return "", filt


def matches(filt: str, topic: str) -> bool:
    """Does the (share-stripped) filter match the topic name?"""
    return match_levels(filt.split("/"), topic.split("/"))


def match_levels(f: list, t: list) -> bool:
    # [MQTT-4.7.2-1]: a filter that opens with a wildcard matches no
    # topic that opens with '$'
    if t[0].startswith("$") and f[0] in ("+", "#"):
        return False
    for i, level in enumerate(f):
        if level == "#":
            return True             # the parent level itself included
        if i >= len(t):
            return False
        if level != "+" and level != t[i]:
            return False
    return len(f) == len(t)


class Reference:
    """``plan``: subscriber id -> [(filter, qos)]."""

    def __init__(self, plan: dict) -> None:
        self.rows = []              # (client, group, filter levels, qos)
        for cid, subs in plan.items():
            for filt, qos in subs:
                group, plain = split_share(filt)
                self.rows.append((cid, group, plain.split("/"), qos))
        self._memo: dict = {}

    def receivers(self, topic: str) -> tuple[dict, dict]:
        """(client -> granted qos for plain subscriptions, the highest
        of a client's matching filters; share group -> {member -> qos})."""
        hit = self._memo.get(topic)
        if hit is not None:
            return hit
        plain: dict = {}
        shared: dict = {}
        levels = topic.split("/")
        for cid, group, filt, qos in self.rows:
            if not match_levels(filt, levels):
                continue
            into = shared.setdefault(group, {}) if group else plain
            into[cid] = max(qos, into.get(cid, 0))
        if len(self._memo) < 65536:
            self._memo[topic] = (plain, shared)
        return plain, shared
