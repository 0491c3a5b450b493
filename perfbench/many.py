#!/usr/bin/env python3
"""Several runs of cells in one call, one after the other, each a new
process (this one never touches JAX, so each run gets the chip):

    python perfbench/many.py --out chiprun_out/flood --seconds 20 \\
        fleet-1m.flood:11:0 fleet-1m.flood:12:0 fleet-1m.flood:11:1

Each run is ``<cell>:<seed>:<trace>``. A run's whole output goes to
``<out>/<n>-<cell>-<seed>-t<trace>.log`` and its last line to
``<out>/lines.jsonl``; at the end every metric's median and spread
(distance between the quartiles of ``statistics.quantiles(n=4)`` over the
median) are printed per cell, as the bounds are set from them.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    lines = []
    for n, spec in enumerate(args.runs):
        cell, seed, trace = spec.split(":")
        tag = f"{n}-{cell}-{seed}-t{trace}"
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               cell, "--seed", seed, "--seconds", str(args.seconds),
               "--trace", trace]
        if args.keep_trace and trace == "1":
            cmd += ["--keep-trace", os.path.join(args.out, tag + ".xplane.pb")]
        t0 = time.monotonic()
        with open(os.path.join(args.out, tag + ".log"), "w") as log:
            rc = subprocess.run(cmd, stdout=log,
                                stderr=subprocess.STDOUT).returncode
        with open(os.path.join(args.out, tag + ".log")) as log:
            tail = log.read().strip().splitlines()
        last = tail[-1] if tail else ""
        took = time.monotonic() - t0
        print(f"{tag} rc {rc} {took:.0f} s: {last[:1500]}", flush=True)
        try:
            line = json.loads(last)
        except ValueError:
            print("\n".join(tail[-15:]), flush=True)
            continue
        line.update(run=spec, rc=rc, wall_s=took)
        lines.append(line)
        with open(os.path.join(args.out, "lines.jsonl"), "a") as fh:
            fh.write(json.dumps(line) + "\n")
    by: dict = {}
    for line in lines:
        cell, _seed, trace = line["run"].split(":")
        for name, m in line["metrics"].items():
            by.setdefault((cell, trace, name), []).append(m["value"])
    for (cell, trace, name), vals in sorted(by.items()):
        sp = f"{100 * spread(vals):6.2f}%" if len(vals) >= 2 and \
            statistics.median(vals) else "      -"
        print(f"{cell} t{trace} {name:34s} n={len(vals)} median "
              f"{statistics.median(vals):12.4f} spread {sp} "
              f"[{min(vals):.4f} .. {max(vals):.4f}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
