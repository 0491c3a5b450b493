"""The end-to-end metrics, one function each, named as in
``BENCHMARK.json``. ``run`` is what ``run.py`` measured: the window's
length, the joined check (``check.join``) and the set-up clocks. All of
it is the host's clock, taken by the benchmark itself."""

from __future__ import annotations

import arith


def delivered_rate(run: dict) -> float:
    """Right deliveries that reached a live subscriber's socket inside
    the window, per second of window."""
    return run["joined"]["in_window"] / run["seconds"]


def deliver_p50_ms(run: dict) -> float:
    """Median of due time -> arrival over every delivery of the
    messages sent in the window."""
    return arith.percentile(run["joined"]["latencies_ns"], 50) / 1e6


def deliver_p99_ms(run: dict) -> float:
    return arith.percentile(run["joined"]["latencies_ns"], 99) / 1e6


def setup_s(run: dict) -> float:
    """Process start -> the window's first message is due."""
    return run["window_opens_s"]
