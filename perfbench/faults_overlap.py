#!/usr/bin/env python3
"""``faults.py`` for a cell whose sessions hold filters that overlap
(``tree-100k.lockstep``: a fifth of the deliveries reach a session
through two or three of its filters, some at different QoS). The
guarantee such a deployment states is "one copy a session a message
whatever the number of its filters that match, at the highest of their
QoS" [MQTT-3.3.5-1]. ``stranger`` and ``drop`` apply there as they are;
this adds the two faults that break that guarantee alone, and everything
else is ``faults.py``'s (same arguments, same run, its own faults too):

    python perfbench/faults_overlap.py overlap_twice --workload <cell> --seed <n> --seconds <s> --trace 0

``overlap_twice``  a session whose filters overlap on the topic is
                   handed one copy a matching filter, each at that
                   filter's own QoS: a second copy is a delivery to a
                   wrong set.
``overlap_low``    such a session gets its one copy at the lowest QoS
                   its matching filters grant, not the highest: a
                   wrong QoS is a wrong set too.

Which of a session's filters match is worked out here with the plain
reference's rule (``reference.matches``) over the session's own
subscriptions, so neither fault leans on how the program marks a folded
subscription.
"""

from __future__ import annotations

import dataclasses
import sys

import faults
from reference import matches


def _overlapping(client, packet) -> list:
    """The client's own plain subscriptions that match the publish's
    topic, where there are two or more; else nothing."""
    subs = [s for f, s in client.subscriptions.items()
            if not f.startswith("$share/") and matches(f, packet.topic)]
    return subs if len(subs) > 1 else []


def overlap_twice() -> None:
    from maxmq_tpu.broker.server import Broker
    once = Broker._publish_to_client

    def per_filter(self, client, sub, packet, shared, fan=None) -> None:
        own = [] if shared else _overlapping(client, packet)
        for each in own or [sub]:
            once(self, client, each, packet, shared, fan)
    Broker._publish_to_client = per_filter


def overlap_low() -> None:
    from maxmq_tpu.broker.server import Broker
    once = Broker._publish_to_client

    def lowest(self, client, sub, packet, shared, fan=None) -> None:
        own = [] if shared else _overlapping(client, packet)
        if own:
            sub = dataclasses.replace(sub, qos=min(s.qos for s in own))
        once(self, client, sub, packet, shared, fan)
    Broker._publish_to_client = lowest


faults.FAULTS["overlap_twice"] = overlap_twice
faults.FAULTS["overlap_low"] = overlap_low

if __name__ == "__main__":
    sys.exit(faults.main())
