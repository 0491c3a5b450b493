"""The event loop's books on the benchmark's line (ISSUE 36): eleven
per-layer metrics read from the ring's ``loop_*`` stages by the reader
that is there. Their entries and files agree, under the rules
``test_files.py`` holds every metric to, and a rehearsal of one
closed-loop and one open-loop cell on the CPU prints each of the cell's
new metrics with a value above 0 (results, not speeds)."""

import json
import os

import pytest

import readers
from rehearsal import BENCH, bench, rehearse

RATE_CELLS = ["fleet-1m.flood", "fleet-1m.hot10k", "fleet-fanout-1k.flood",
              "fleet-fanin-500.flood"]
LATENCY_CELLS = ["fleet-1m.steady", "sparkplug-plant.steady"]
RATE = {f"loop_{s}_us.rate" for s in
        ("busy", "read", "deliver", "pass", "flush", "ack", "other",
         "offcpu")}
LATENCY = {f"loop_{s}_us.latency" for s in ("busy", "other", "offcpu")}


def layer(name: str) -> dict:
    with open(os.path.join(BENCH, "layers", name + ".json")) as fh:
        return json.load(fh)


def test_eleven_entries_eleven_files_and_they_agree():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    reported = {c: {m["name"] for m in b["end_to_end"]
                    if c in m.get("workloads", cells)} for c in cells}
    mine = [m for m in b["per_layer"] if m["name"] in RATE | LATENCY]
    assert {m["name"] for m in mine} == RATE | LATENCY and len(mine) == 11
    # in one block, appended after everything that was there then (a
    # later cell's metrics may follow it)
    start = b["per_layer"].index(mine[0])
    assert b["per_layer"][start:start + 11] == mine
    for m in mine:
        rate = m["name"] in RATE
        # the cells of then first; a later cell may be added to a list
        cells = RATE_CELLS if rate else LATENCY_CELLS
        assert m["workloads"][:len(cells)] == cells
        assert m == {
            "name": m["name"], "unit": "us", "better": "lower",
            "source": "program_span", "layer": "event loop",
            "moves": "delivered_rate" if rate else "deliver_p50_ms",
            "workloads": m["workloads"]}
        stage = m["name"].split("_us.")[0]
        assert layer(m["name"]) == {
            "layer": m["layer"], "moves": m["moves"], "unit": m["unit"],
            "reader": "ring_stage_median", "args": {"stage": stage}}
        assert callable(getattr(readers, layer(m["name"])["reader"]))
        for cell in m["workloads"]:
            assert m["moves"] in reported[cell], (m["name"], cell)
    # the layer is the one the ring's loop_lag metrics already name
    lag = next(m for m in b["per_layer"] if m["name"] == "loop_lag_ms.flood")
    assert lag["layer"] == "event loop"


def test_the_reader_leaves_a_parents_ring_out_and_keeps_tenths():
    parent = {"ring": [{"spans": [{"stage": "loop_lag", "dur_us": 120},
                                  {"stage": "flush", "dur_us": 900}],
                        "drains": []}]}
    change = {"ring": [
        {"spans": [{"stage": "loop_busy", "dur_us": 393.4, "calls": 2.1},
                   {"stage": "loop_offcpu", "dur_us": 0.0}], "drains": []},
        {"spans": [{"stage": "loop_busy", "dur_us": 401.2, "calls": 2.0},
                   {"stage": "loop_offcpu", "dur_us": 12.5}], "drains": []},
        {"spans": [{"stage": "loop_lag", "dur_us": 7}], "drains": []}]}
    for name in sorted(RATE | LATENCY):
        assert readers.ring_stage_median(parent, **layer(name)["args"]) \
            is None, name
    busy = layer("loop_busy_us.rate")["args"]
    assert readers.ring_stage_median(change, **busy) == \
        pytest.approx(397.3)
    # a state that did nothing reads a true 0, not absent
    offcpu = layer("loop_offcpu_us.latency")["args"]
    assert readers.ring_stage_median(change, **offcpu) == 6.25
    assert readers.ring_stage_median(
        change, **layer("loop_pass_us.rate")["args"]) is None


@pytest.mark.parametrize("cell,mine", [
    ("fleet-fanin-500.flood", RATE), ("fleet-1m.steady", LATENCY)])
def test_a_traced_rehearsal_prints_the_cells_loop_metrics(cell, mine):
    line, failures = rehearse(cell, 1)
    assert failures == "['platform is cpu, not tpu']"
    named = {m["name"] for m in bench()["per_layer"]
             if cell in m.get("workloads", [cell])}
    assert mine <= named and set(line["metrics"]) == named
    assert not (RATE | LATENCY) - mine & set(line["metrics"])
    for name in sorted(mine):
        got = line["metrics"][name]
        assert got["unit"] == "us" and got["value"] > 0, (name, got)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    suffix = ".rate" if mine is RATE else ".latency"
    # what a part reads is a part of the whole
    assert m["loop_other_us" + suffix] < m["loop_busy_us" + suffix]
    assert m["loop_offcpu_us" + suffix] < m["loop_busy_us" + suffix]
    if mine is RATE:
        parts = sum(m[f"loop_{s}_us.rate"] for s in
                    ("read", "deliver", "pass", "flush", "ack", "other"))
        # medians of parts, and share, poll, batch and settle beside
        # them: near the whole, not equal to it
        assert 0.6 * m["loop_busy_us.rate"] < parts \
            < 1.1 * m["loop_busy_us.rate"]
    # the line's breakdown sums the spans over the sampled publishes
    stages = dict(line["breakdown"]["idle_gaps"])
    assert any(k.startswith("host_stage_loop_") for k in stages)
