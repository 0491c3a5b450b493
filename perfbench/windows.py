#!/usr/bin/env python3
"""One boot, then windows of an open-loop cell's traffic one after the
other: how a window's latency depends on when it is taken.

    python perfbench/windows.py --workload fleet-1m.steady --seed 5 \\
        --windows 20:1000,20:1000,20:500

Each window is ``<seconds>:<msgs/s>``; the first follows the cell's own
warm-up, so it is the window ``run.py`` measures. A line of JSON a
window: quantiles of due -> arrival over every delivery, the median by
2 s of due time (``slices``), the generators' own lateness (median of
sent - due: it rises with the host's wake-up latency, whatever the
broker does) and the counters that say who answered. This is how PR 27
found that the first half-minute after a boot reads 1.7 x the later
windows, and that a shared host has episodes a whole host has not
(PERF.md section 6). Not part of a run; ``correct`` is not decided here.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import run  # the cell's files, the broker and the generators
import arith
import check

SLICE_S = 2.0
COUNTERS = ("batches", "batched_topics", "bypasses", "cache_hits", "matches",
            "host_matches", "deadline_fallbacks")


def describe(dumps: list, t0: int, t1: int) -> dict:
    """Latency of every delivery of the window's messages, as the
    subscribers stamped it (arrival - due, from the payload's head)."""
    sent = {(r[0], r[1]): r for d in dumps for r in d["sent"]}
    slices = [[] for _ in range(int(round((t1 - t0) / 1e9 / SLICE_S)))]
    lat = []
    for d in dumps:
        for recs in d["got"].values():
            for head, arrival, _flags in recs:
                pub, seq, due = (int(x) for x in head.split(b":"))
                if (pub, seq) not in sent:
                    continue        # a straggler of an earlier window
                lat.append(arrival - due)
                k = int((due - t0) / 1e9 / SLICE_S)
                if 0 <= k < len(slices):
                    slices[k].append(arrival - due)
    late = [r[5] - r[4] for r in sent.values()]
    out = {f"p{q}_ms": arith.percentile(lat, q) / 1e6
           for q in (10, 50, 90, 99)}
    out.update(messages=len(sent), deliveries=len(lat),
               slices_p50_ms=[round(arith.median(s) / 1e6, 3) if s else None
                              for s in slices],
               gen_late_p50_ms=arith.median(late) / 1e6,
               gen_late_max_ms=max(late) / 1e6)
    return out


async def windows(args, cell: dict, workdir: str) -> None:
    failures: list = []
    served = run.Served(cell, args.seed, workdir, False, failures)
    try:
        await served.boot()
        await served.connect()
        await served.phase("warm", run.WARM_SECONDS)
        for n, spec in enumerate(args.windows.split(",")):
            seconds, rate = (float(x) for x in spec.split(":"))
            before = run.counters(served.broker)
            t0, _replies, dumps, _ = await served.phase(
                f"w{n}", seconds, extra=str(rate))
            after = run.counters(served.broker)
            t1 = t0 + int(seconds * 1e9)
            line = describe(dumps, t0, t1)
            joined = check.join(dumps, served.groups, t0, t1)
            line.update(window=n, seconds=seconds, rate=rate,
                        failed=joined["failed"],
                        failures=joined["failures"] + failures,
                        **{k: after[k] - before[k] for k in COUNTERS})
            print(json.dumps(line), flush=True)
    finally:
        await served.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--windows", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = run.load_cell(args.workload, args.rehearse)
    if cell["traffic"]["loop"] != "open":
        raise SystemExit("windows.py is for an open-loop cell")
    subprocess.run(["make", "-C", os.path.join(run.ROOT, "native")],
                   check=True, stdout=sys.stderr)
    from maxmq_tpu.accel import place_compile_cache
    place_compile_cache()
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"windows: no TPU ({platform}); nothing was run",
              file=sys.stderr)
        return 3
    from maxmq_tpu.bootstrap import install_event_loop
    from maxmq_tpu.utils.config import Config
    install_event_loop(Config().broker_event_loop)
    workdir = tempfile.mkdtemp(prefix="perfbench-windows-")
    t = time.monotonic()
    try:
        asyncio.run(windows(args, cell, workdir))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"windows: {time.monotonic() - t:.0f} s in all", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
