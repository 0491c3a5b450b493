#!/usr/bin/env python3
"""A run of a cell with the served path broken underneath, to show that
the check sees it: the control and the planted faults of PERF.md
section 2, "How correct is decided".

    python perfbench/faults.py <fault> --workload <cell> --seed <n> --seconds <s> --trace 0

Everything after the fault's name goes to ``run.py`` as it is; the run
is ``run.py``'s own (same boot, same traffic, same window, same check)
but for one method of the broker, replaced just before the boot. A run
so broken has to end with ``correct`` false and a failure of the check
(not of the platform alone) among its failures. The benchmark's own runs
never come through here.

``share_twice``  the control: one guarantee of the configuration broken
                 ("each $share group is served exactly once per matching
                 message"): the group's pick runs twice, so the rotation
                 serves a second member.
``stranger``     an answer altered where it is produced: every 50th
                 match result that reaches somebody also names a live
                 subscriber that holds no matching subscription.
``drop``         half of the work left out: every second plain delivery
                 is not made (a QoS 1 one never arrives; a QoS 0 one
                 counts as a failed operation).
"""

from __future__ import annotations

import itertools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def share_twice() -> None:
    from maxmq_tpu.broker.server import Broker
    once = Broker._fan_out_shared

    def twice(self, shared, pairs, packet) -> None:
        once(self, shared, pairs, packet)
        once(self, shared, pairs, packet)
    Broker._fan_out_shared = twice


def stranger() -> None:
    from maxmq_tpu.broker.client import ClientRegistry
    resolve = ClientRegistry.resolve
    seen = itertools.count(1)

    def altered(self, result):
        pairs, shared, matched, resolved = resolve(self, result)
        if pairs and next(seen) % 50 == 0:
            have = {client.id for client, _sub in pairs}
            other = next((c for c in self._clients.values()
                          if c.subscriptions and not c.closed
                          and c.id not in have), None)
            if other is not None:
                pairs = list(pairs) + [(other, pairs[0][1])]
        return pairs, shared, matched, resolved
    ClientRegistry.resolve = altered


def drop() -> None:
    from maxmq_tpu.broker.client import ClientRegistry
    resolve = ClientRegistry.resolve
    made = itertools.count()

    def halved(self, result):
        pairs, shared, matched, resolved = resolve(self, result)
        return ([p for p in pairs if next(made) % 2], shared, matched,
                resolved)
    ClientRegistry.resolve = halved


FAULTS = {"share_twice": share_twice, "stranger": stranger, "drop": drop}


def main() -> int:
    if len(sys.argv) < 2 or sys.argv[1] not in FAULTS:
        print(f"usage: faults.py {{{'|'.join(FAULTS)}}} <run.py's arguments>",
              file=sys.stderr)
        return 2
    plant = FAULTS[sys.argv.pop(1)]
    boot = run.Served.boot

    async def broken_boot(self) -> None:
        # here and not earlier: run.main() has built the native
        # libraries by now, and the package loads them once
        plant()
        run.say(f"FAULT PLANTED: {plant.__name__}")
        await boot(self)
    run.Served.boot = broken_boot
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
