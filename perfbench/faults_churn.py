#!/usr/bin/env python3
"""``faults.py`` for a cell whose traffic file declares ``churn``:
clean-start clients that SUBSCRIBE and UNSUBSCRIBE inside the window.
The guarantees such a deployment states are those of MQTT 3.1.1 sections
3.8.4 and 3.10.4: once the SUBACK is sent, the subscription is in force;
once the UNSUBACK is sent, it is not. This adds the two faults that break
them, and everything else is ``faults.py``'s (same arguments, same run,
its own faults too):

    python perfbench/faults_churn.py churn_ghost --workload <cell> --seed <n> --seconds <s> --trace 0

``churn_ghost``  an UNSUBSCRIBE is acknowledged but its filter stays in
                 the topic index: deliveries after the UNSUBACK follow,
                 each a delivery to a wrong set.
``churn_deaf``   a SUBSCRIBE is acknowledged but its filter reaches the
                 topic index only DEAF_SECONDS later: deliveries that
                 were due go missing (``failed``; a QoS 1 one is lost).
"""

from __future__ import annotations

import asyncio
import sys

import faults

DEAF_SECONDS = 2.0


def _replace(ptype: str, make) -> None:
    """Put ``make(handler)`` in the broker's dispatch table in place of
    the handler of that packet type."""
    from maxmq_tpu.broker.server import Broker
    from maxmq_tpu.protocol.codec import PacketType
    key = getattr(PacketType, ptype)
    Broker._DISPATCH[key] = make(Broker._DISPATCH[key])


def churn_ghost() -> None:
    def make(process):
        def ghost(self, client, packet) -> None:
            topics = self.topics
            topics.unsubscribe = lambda client_id, filt: True
            try:
                process(self, client, packet)
            finally:
                del topics.unsubscribe
        return ghost
    _replace("UNSUBSCRIBE", make)


def churn_deaf() -> None:
    def make(process):
        def deaf(self, client, packet) -> None:
            topics = self.topics
            later = asyncio.get_running_loop().call_later
            subscribe = topics.subscribe

            def deferred(client_id, sub) -> bool:
                later(DEAF_SECONDS, subscribe, client_id, sub)
                return True
            topics.subscribe = deferred
            try:
                process(self, client, packet)
            finally:
                del topics.subscribe
        return deaf
    _replace("SUBSCRIBE", make)


faults.FAULTS["churn_ghost"] = churn_ghost
faults.FAULTS["churn_deaf"] = churn_deaf

if __name__ == "__main__":
    sys.exit(faults.main())
