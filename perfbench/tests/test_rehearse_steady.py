"""The open-loop cell, rehearsed on the CPU at the rehearsal's table
size: every phase runs, the line carries the cell's metrics by name, and
nothing but the platform stands against ``correct``."""

from rehearsal import bench, rehearse

CELL = "fleet-1m.steady"


def names(kind: str) -> set:
    return {m["name"] for m in bench()[kind]
            if CELL in m.get("workloads", [CELL])}


def test_untraced_line_has_the_end_to_end_metrics():
    line, failures = rehearse(CELL, 0)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("end_to_end")
    assert {"deliver_p50_ms", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] > 1000 and line["failed"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_line_has_every_per_layer_metric():
    line, failures = rehearse(CELL, 1)
    assert failures == "['platform is cpu, not tpu']"
    # (the kernel's metrics need the chip's trace; none is this cell's)
    assert set(line["metrics"]) == names("per_layer")
    assert "window_s" in line["device"] and "breakdown" in line
