"""The reductions a per-layer metric may name as its ``reader`` in
``perfbench/layers/<metric>.json``. Each takes what the traced run
gathered (``run``) and the file's ``args``, and returns a number, or
None where there was nothing to read: the harness then leaves the
metric out of the line (absent, never zero)."""

from __future__ import annotations

import re

import arith
import roofline


def _delta(run: dict, names) -> float:
    before, after = run["counters"]
    return sum(after[k] - before[k] for k in names)


def counter_delta(run: dict, counters: list, scale: float = 1.0):
    return _delta(run, counters) * scale


def counter_ratio(run: dict, num: list, den: list, minus: list = (),
                  scale: float = 1.0):
    """(sum of ``num`` - sum of ``minus``) / sum of ``den`` over the
    window, floored at 0; None where the denominator did not move."""
    d = _delta(run, den)
    if d <= 0:
        return None
    return max(0.0, _delta(run, num) - _delta(run, minus)) / d * scale


def ring_stage_median(run: dict, stage: str, scale: float = 1.0):
    """Median duration of that stage's spans over the publishes the
    tracer sampled inside the window (``dur_us``, so ``scale`` 1 gives
    microseconds and 0.001 milliseconds). A drain span is per
    subscriber; every one counts."""
    key = "drains" if stage == "drain" else "spans"
    durs = [s["dur_us"] for e in run["ring"] for s in e[key]
            if key == "drains" or s["stage"] == stage]
    return arith.median(durs) * scale if durs else None


def boot_seconds(run: dict, key: str):
    return run["boot_seconds"].get(key)


def generator_field(run: dict, field: str, scale: float = 1.0):
    """The largest value any generator process reported for the field."""
    vals = [g[field] for g in run["generators"] if field in g]
    return max(vals) * scale if vals else None


def _kernel(run: dict, match: str):
    trace = run.get("trace")
    if not trace:
        return None
    pat = re.compile(match)
    hits = [(n, c, s) for n, (c, s) in trace["ops"].items() if pat.search(n)]
    if not hits:
        return None
    return sum(c for _n, c, _s in hits), sum(s for _n, _c, s in hits)


def xplane_event_time(run: dict, match: str, scale: float = 1.0):
    """Mean device time of the events whose name matches (seconds x
    ``scale``)."""
    k = _kernel(run, match)
    if k is None:
        return None
    count, seconds = k
    return seconds / count * scale


def kernel_roofline(run: dict, match: str):
    """Share (%) of the HBM roofline: the least time the chip could take
    to move the bytes the kernel's calls must move (``roofline.py``, from
    the engine's plan and table shapes), over the time the trace gives
    them."""
    k = _kernel(run, match)
    plan = run.get("kernel")
    if k is None or not plan:
        return None
    count, seconds = k
    bytes_per_call = roofline.kernel_call_bytes(plan)
    floor = count * bytes_per_call / roofline.peak(run["device_kind"],
                                                   "hbm_bytes_per_s")
    return 100.0 * floor / seconds
