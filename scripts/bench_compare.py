#!/usr/bin/env python
"""Bench-trajectory comparison / regression gate (ADR 017).

The repo accumulates one ``BENCH_r<NN>.json`` per round (the driver's
capture: ``{n, cmd, rc, tail, parsed}``) — and until this script,
nothing read them, which is why the perf trajectory handed to each
round was empty. This tool:

1. loads the newest two rounds, tolerating every historical shape: a
   structured ``parsed`` object, a raw bench row list, or a truncated
   ``tail`` from which the largest complete JSON object is recovered
   via ``raw_decode`` brace-scanning;
2. flattens every ``{"config": ...}`` row into ``config/metric``
   numeric leaves (nested dicts dot-joined, so the ADR-015 ``trace``
   stanza's ``p99_ms`` tails participate);
3. prints a per-config/per-metric delta table between the two rounds;
4. exits non-zero when a **headline throughput** metric (``*per_sec*``,
   higher-better), a **p99 latency** metric (``*p99*``, lower-better),
   or (ADR 020/024) an **SLO-sheet** field — ``*loss*``,
   ``*recover*``/``*convergence*`` times, ``*violation*`` counts, and
   the crashday row's ``*duplicate*`` (QoS2) counts, all
   lower-better — regressed by more than ``--threshold``
   (default 15%).

Latency (``*_ms``) metrics additionally carry an **absolute noise
floor** (``--abs-floor-ms``, default 1.0): the trace stanzas' p99s
come from one fully-sampled tail round, so on sub-millisecond stages
the quantile is effectively the max of a handful of samples and
run-to-run swings of 2-5x are scheduler noise, not regressions. A
``*_ms`` move only gates when it exceeds the threshold *and* moved by
at least the floor in absolute terms — real regressions in the gated
recovery-time fields (hundreds of ms) clear a 1 ms floor trivially;
0.1 -> 0.3 ms tail wobble does not. Sub-floor bad moves still print
as ``worse`` in the table.

CI runs the gate BLOCKING (since ADR 018); the
``BENCH_COMPARE_WARN_ONLY`` env var falls back to report-only — see
docs/observability.md.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys

DEFAULT_THRESHOLD = 0.15
ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


# ----------------------------------------------------------------------
# Loading: every historical BENCH file shape -> a JSON document
# ----------------------------------------------------------------------


def _recover_from_tail(tail: str) -> dict | list | None:
    """The driver keeps only the LAST 2000 chars of bench stdout, so
    the outermost JSON object is usually truncated at the front.
    Scan each ``{``/``[`` and ``raw_decode`` (which tolerates trailing
    garbage); keep the candidate with the most content."""
    dec = json.JSONDecoder()
    best, best_len = None, 0
    starts = [m.start() for m in re.finditer(r"[{\[]", tail)][:64]
    for i in starts:
        try:
            obj, end = dec.raw_decode(tail[i:])
        except ValueError:
            continue
        if isinstance(obj, (dict, list)) and end > best_len:
            best, best_len = obj, end
    return best


def load_round(path: str):
    """One bench file -> (label, document-or-None)."""
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    if isinstance(doc, dict):
        if doc.get("parsed") is not None:
            return doc["parsed"]
        if isinstance(doc.get("tail"), str):
            return _recover_from_tail(doc["tail"])
    return doc


# ----------------------------------------------------------------------
# Extraction: document -> {config: {metric: float}}
# ----------------------------------------------------------------------


def _flatten(d: dict, prefix: str, out: dict) -> None:
    for k, v in d.items():
        key = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[key] = float(v)
        elif isinstance(v, dict):
            _flatten(v, key, out)


def extract_rows(doc) -> dict[str, dict[str, float]]:
    """Walk any bench document collecting every ``{"config": ...}``
    row (flattened to numeric leaves) plus a ``_headline`` row for the
    driver's top-level {metric, value} summary."""
    rows: dict[str, dict[str, float]] = {}

    def walk(node) -> None:
        if isinstance(node, list):
            for item in node:
                walk(item)
            return
        if not isinstance(node, dict):
            return
        cfg = node.get("config")
        if isinstance(cfg, str):
            flat: dict[str, float] = {}
            _flatten(node, "", flat)
            flat.pop("config", None)
            rows.setdefault(cfg, {}).update(flat)
        if isinstance(node.get("metric"), str) and isinstance(
                node.get("value"), (int, float)):
            rows.setdefault("_headline", {})[node["metric"]] = \
                float(node["value"])
        for v in node.values():
            if isinstance(v, (dict, list)):
                walk(v)

    walk(doc)
    return rows


# ----------------------------------------------------------------------
# Comparison
# ----------------------------------------------------------------------


def _direction(metric: str) -> int:
    """+1 = higher is better, -1 = lower is better, 0 = informational."""
    m = metric.lower()
    if "per_sec" in m or "per_s" in m:
        return 1
    if m.endswith("_ms") or m.endswith("_s") or "latency" in m:
        return -1
    # ADR 020: SLO-sheet counters — loss windows, recovery /
    # convergence times, violation counts — are all lower-better;
    # ADR 024 adds duplicate counts (QoS2 exactly-once across
    # crashes). "duplicate", not "dup": "speedup" contains "dup" and
    # the cshard speedup ratios must stay informational
    if "loss" in m or "recover" in m or "convergence" in m \
            or "violation" in m or "duplicate" in m:
        return -1
    return 0


def _gated(metric: str) -> bool:
    """Headline throughput, p99 tails, and (ADR 020) the macroday SLO
    sheet's loss / recovery-time fields gate the exit code."""
    m = metric.lower()
    return ("per_sec" in m or "p99" in m or "loss" in m
            or "recover" in m or "convergence" in m
            or "violation" in m or "duplicate" in m)


def compare(old: dict, new: dict, threshold: float,
            abs_floor_ms: float = 1.0):
    """-> (table_rows, regressions). A regression is a gated metric
    moving >threshold in its bad direction — and, for ``*_ms``
    latencies, by at least ``abs_floor_ms`` in absolute terms (the
    tail-round p99s are max-of-few-samples on sub-ms stages; see the
    module docstring). Sub-floor bad moves flag ``worse`` only.

    ADR 022: a config that declares a WAN round trip (an ``rtt_ms``
    key in its row — the geoday sheet) gets the floor SCALED by that
    RTT: at 150ms configured RTT a recovery time can legitimately
    wobble by a whole round trip between runs, so the absolute floor
    for its ``*_ms`` fields is ``abs_floor_ms x rtt_ms`` — the
    relative threshold still applies on top."""
    table, regressions = [], []
    for cfg in sorted(set(old) & set(new)):
        rtt = new[cfg].get("rtt_ms") or old[cfg].get("rtt_ms") or 0.0
        floor_ms = max(abs_floor_ms, abs_floor_ms * rtt) \
            if isinstance(rtt, (int, float)) else abs_floor_ms
        for metric in sorted(set(old[cfg]) & set(new[cfg])):
            a, b = old[cfg][metric], new[cfg][metric]
            d = _direction(metric)
            if d == 0:
                continue
            if a == 0:
                delta = 0.0 if b == 0 else math.inf
            else:
                delta = (b - a) / abs(a)
            bad = (d > 0 and delta < -threshold) or \
                  (d < 0 and delta > threshold)
            gates = bad and _gated(metric)
            if gates and metric.lower().endswith("_ms") \
                    and (b - a) < floor_ms:
                gates = False
            flag = ""
            if bad:
                flag = "REGRESSION" if gates else "worse"
                if gates:
                    regressions.append((cfg, metric, a, b, delta))
            table.append((cfg, metric, a, b, delta, flag))
    return table, regressions


def find_rounds(root: str) -> list[str]:
    files = glob.glob(os.path.join(root, "BENCH_r*.json"))
    keyed = []
    for f in files:
        m = ROUND_RE.search(os.path.basename(f))
        if m:
            keyed.append((int(m.group(1)), f))
    return [f for _n, f in sorted(keyed)]


def _fmt_val(v: float) -> str:
    return f"{v:,.3f}".rstrip("0").rstrip(".") or "0"


def render(table, old_label: str, new_label: str) -> str:
    lines = [f"bench delta: {old_label} -> {new_label}",
             f"{'config':28} {'metric':44} {'old':>14} {'new':>14} "
             f"{'delta':>9}  flag"]
    for cfg, metric, a, b, delta, flag in table:
        pct = ("inf" if math.isinf(delta) else f"{delta * 100:+.1f}%")
        lines.append(f"{cfg[:28]:28} {metric[:44]:44} "
                     f"{_fmt_val(a):>14} {_fmt_val(b):>14} "
                     f"{pct:>9}  {flag}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="explicit bench JSONs (oldest first); default "
                         "= the newest two BENCH_r*.json in --root")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="repo root to scan")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="regression threshold as a fraction "
                         "(default 0.15)")
    ap.add_argument("--abs-floor-ms", type=float, default=1.0,
                    help="*_ms metrics only gate when they also moved "
                         "by at least this many ms (default 1.0) — "
                         "sub-ms tail-round p99s are max-of-few-samples "
                         "noise")
    ap.add_argument("--warn-only", action="store_true",
                    default=bool(os.environ.get("BENCH_COMPARE_WARN_ONLY")),
                    help="always exit 0 (report mode). CI runs the gate "
                         "BLOCKING since ADR 018; set the "
                         "BENCH_COMPARE_WARN_ONLY env var (any non-empty "
                         "value) as the escape hatch on known-noisy boxes")
    args = ap.parse_args(argv)

    paths = args.files or find_rounds(args.root)[-2:]
    if len(paths) < 2:
        print("bench-compare: fewer than two usable rounds; nothing "
              "to compare", file=sys.stderr)
        return 0
    old_path, new_path = paths[-2], paths[-1]
    rows = []
    for p in (old_path, new_path):
        doc = load_round(p)
        rows.append(extract_rows(doc) if doc is not None else {})
    old_rows, new_rows = rows
    if not old_rows or not new_rows:
        print(f"bench-compare: no extractable rows "
              f"(old={len(old_rows)} cfgs, new={len(new_rows)} cfgs); "
              f"skipping", file=sys.stderr)
        return 0
    table, regressions = compare(old_rows, new_rows, args.threshold,
                                 args.abs_floor_ms)
    print(render(table, os.path.basename(old_path),
                 os.path.basename(new_path)))

    if regressions:
        print(f"\n{len(regressions)} regression(s) past "
              f"{args.threshold * 100:.0f}%:", file=sys.stderr)
        for cfg, metric, a, b, delta in regressions:
            print(f"  {cfg}/{metric}: {_fmt_val(a)} -> {_fmt_val(b)} "
                  f"({delta * 100:+.1f}%)", file=sys.stderr)
        return 0 if args.warn_only else min(len(regressions), 125)
    print("\nno gated regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
