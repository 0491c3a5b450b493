#!/usr/bin/env python3
"""``faults.py`` for a cell in which every delivery is a ``$share`` pick
(``fleet-fanin-500.flood``: 500 sessions in one group, no plain
subscription). There ``stranger`` and ``drop`` plant nothing: both alter
the plain ``pairs`` of a resolved match result, and such a cell has
none. ``share_twice`` (the group served twice) works there as it is.
This adds the one fault such a cell still needs, and everything else is
``faults.py``'s (same arguments, same run, its own faults too):

    python perfbench/faults_share.py share_skip --workload <cell> --seed <n> --seconds <s> --trace 0

``share_skip``   half of the work left out: every second ``$share`` pick
                 is made (the rotation moves on) and not delivered, so
                 the group is served by nobody: a QoS 1 delivery never
                 arrives, a QoS 0 one counts as a failed operation.
"""

from __future__ import annotations

import itertools
import sys

import faults


def share_skip() -> None:
    from maxmq_tpu.matching.trie import TopicIndex
    select = TopicIndex.select_shared
    made = itertools.count()

    def skipping(self, group, filter_, candidates, alive=None):
        pick = select(self, group, filter_, candidates, alive)
        return pick if next(made) % 2 else None
    TopicIndex.select_shared = skipping


faults.FAULTS["share_skip"] = share_skip

if __name__ == "__main__":
    sys.exit(faults.main())
