#!/usr/bin/env python3
"""``faults.py`` for a cell in which every live session receives every
message that reaches anybody (``fleet-fanout-1k.flood``: 1,000 sessions
on all five broadcast topics). There ``stranger`` plants nothing: a
match result that reaches somebody already names every session that
holds a subscription, so there is no stranger left to add. This adds the
one fault such a cell still needs, and everything else is ``faults.py``'s
(same arguments, same run, its own faults too):

    python perfbench/faults_wide.py uninvited --workload <cell> --seed <n> --seconds <s> --trace 0

``uninvited``    a delivery nobody asked for: every 10th client publish
                 whose match result reaches nobody is also delivered to
                 one live subscriber, under one of its own subscriptions
                 (a message on a topic its receiver never subscribed to).
"""

from __future__ import annotations

import itertools
import sys

import faults


def uninvited() -> None:
    from maxmq_tpu.broker.server import Broker
    local = Broker._fan_out_local
    seen = itertools.count(1)

    def altered(self, subscribers, packet) -> None:
        local(self, subscribers, packet)
        pairs, shared, _matched, _resolved = self.clients.resolve(subscribers)
        if (pairs or shared or packet.topic.startswith("$")
                or next(seen) % 10):
            return
        guest = next((c for c in self.clients.all()
                      if c.subscriptions and not c.closed), None)
        if guest is not None:
            self._publish_to_client(
                guest, next(iter(guest.subscriptions.values())), packet,
                shared=False)
    Broker._fan_out_local = altered


faults.FAULTS["uninvited"] = uninvited

if __name__ == "__main__":
    sys.exit(faults.main())
