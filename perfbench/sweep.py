#!/usr/bin/env python3
"""Find the knee of an open-loop cell once: one boot, then steps of the
cell's own traffic at rising rates.

    python perfbench/sweep.py --workload fleet-1m.steady --seed 5

Steps double from ``--start`` msgs/s until one is not sustained, then two
more halve the gap between the last sustained rate and the first that was
not. A step is sustained when nothing failed or was wrong, queueing has
not set in (median latency <= 2 x the first, lowest step's), the delivery
lag did not grow (median latency of the last quarter of the step's
messages <= 2 x that of the first quarter, or under 5 ms anyway), no
topic fell back at the supervisor's deadline, and the generators kept
their schedule (lateness p99 under 5 ms). The cell's traffic file then
takes 0.8 x the highest sustained rate, by hand; the table goes into
PERF.md. Every line printed is a step; the last is the whole sweep as
JSON.
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

import run  # the cell's files, the broker and the generators
import arith
import check


def judge(step: dict, unloaded_p50_ms: float) -> bool:
    return (not step["failures"] and step["failed"] == 0
            and step["deadline_fallbacks"] == 0
            and step["p50_ms"] <= 2 * unloaded_p50_ms
            and (step["last_quarter_p50_ms"] <= 2 * step["first_quarter_p50_ms"]
                 or step["last_quarter_p50_ms"] < 5.0)
            and step["gen_late_p99_ms"] < 5.0)


async def one_step(served, rate: float, seconds: float, n: int) -> dict:
    before = run.counters(served.broker)
    t0, replies, dumps, _ = await served.phase(f"step{n}", seconds,
                                               extra=str(rate))
    after = run.counters(served.broker)
    joined = check.join(dumps, served.groups, t0, t0 + int(seconds * 1e9))
    lat = joined["latencies_ns"]
    # latencies come out in the order of the sent records, not of time:
    # take the quarters by the deliveries' own due times
    by_due = sorted(zip(joined["due_ns"], lat))
    q = max(1, len(by_due) // 4)
    step = {"rate": rate, "messages": joined["messages"],
            "deliveries": joined["deliveries"], "failed": joined["failed"],
            "failures": joined["failures"],
            "p50_ms": arith.percentile(lat, 50) / 1e6 if lat else None,
            "p99_ms": arith.percentile(lat, 99) / 1e6 if lat else None,
            "first_quarter_p50_ms": arith.median(
                [v for _d, v in by_due[:q]]) / 1e6 if by_due else 0.0,
            "last_quarter_p50_ms": arith.median(
                [v for _d, v in by_due[-q:]]) / 1e6 if by_due else 0.0,
            "gen_late_p99_ms": max(r.get("gen_late_p99_ms", 0.0)
                                   for r in replies),
            "gen_cpu_share": max(r["gen_cpu_share"] for r in replies),
            "offered": joined["messages"] / seconds,
            "breaker": served.broker.matcher.breaker_state_name}
    for k in ("deadline_fallbacks", "breaker_trips", "batches",
              "batched_topics", "bypasses", "cache_hits"):
        step[k] = after[k] - before[k]
    return step


async def sweep(args, cell: dict, workdir: str, steps: list) -> None:
    failures: list = []
    served = run.Served(cell, args.seed, workdir, False, failures)
    try:
        await served.boot()
        await served.connect()
        await served.phase("warm", run.WARM_SECONDS, extra=str(args.start))
        rate, good, bad = float(args.start), None, None
        while len(steps) < args.max_steps:
            step = await one_step(served, rate, args.step_seconds, len(steps))
            steps.append(step)
            step["sustained"] = judge(step, steps[0]["p50_ms"])
            print(json.dumps(step), flush=True)
            if step["sustained"]:
                good = rate if good is None else max(good, rate)
            else:
                bad = rate if bad is None else min(bad, rate)
                await asyncio.sleep(5.0)    # let the backlog and the
                                            # breaker settle
            if bad is None:
                rate *= 2
            elif good is None or bad / good < 1.2:
                break
            else:
                rate = (good + bad) / 2
        if failures:
            print(f"failures: {failures}", flush=True)
    finally:
        await served.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--start", type=float, default=250.0)
    ap.add_argument("--step-seconds", type=float, default=8.0)
    ap.add_argument("--max-steps", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = run.load_cell(args.workload, args.rehearse)
    if cell["traffic"]["loop"] != "open":
        raise SystemExit("the sweep is for an open-loop cell")
    subprocess.run(["make", "-C", os.path.join(run.ROOT, "native")],
                   check=True, stdout=sys.stderr)
    from maxmq_tpu.accel import place_compile_cache
    place_compile_cache()
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"sweep: no TPU ({platform}); nothing was run", file=sys.stderr)
        return 3
    from maxmq_tpu.bootstrap import install_event_loop
    from maxmq_tpu.utils.config import Config
    install_event_loop(Config().broker_event_loop)
    workdir = tempfile.mkdtemp(prefix="perfbench-sweep-")
    steps: list = []
    try:
        asyncio.run(sweep(args, cell, workdir, steps))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    good = [s["rate"] for s in steps if s["sustained"]]
    print(json.dumps({"platform": platform, "workload": args.workload,
                      "seed": args.seed, "step_seconds": args.step_seconds,
                      "knee": max(good) if good else None,
                      "suggested_rate": 0.8 * max(good) if good else None,
                      "steps": steps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
