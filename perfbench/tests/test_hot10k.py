"""The cell ``fleet-1m.hot10k``: the flood's loop with repeating topics.
Its traffic file is ``fleet-flood.json`` but for the topics, and it
rehearses on the CPU to a line with every metric of its own."""

import json
import os

from rehearsal import BENCH, bench, rehearse

CELL = "fleet-1m.hot10k"


def names(kind: str) -> set:
    return {m["name"] for m in bench()[kind]
            if CELL in m.get("workloads", [CELL])}


def traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


def test_cell_and_traffic_are_the_floods_with_repeating_topics():
    cell = next(w for w in bench()["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("fleet-1m", "fleet-hot10k", 1)
    hot, flood = traffic("fleet-hot10k"), traffic("fleet-flood")
    for key in ("loop", "publishers", "in_flight", "qos1_share",
                "payload_bytes", "drain_grace_s"):
        assert hot[key] == flood[key], key
    assert hot["topics"] == [
        {"recipe": "zipf_pool", "share": 0.5,
         "args": {"pool": 10000, "s": 1.0}},
        {"recipe": "live_hits", "share": 0.5}]
    assert names("end_to_end") == {"delivered_rate", "setup_s"}
    assert {n for n in names("per_layer") if n.endswith(".hot10k")} == {
        "gen_cpu_share.hot10k", "loop_lag_ms.hot10k",
        "stage_fanout_us.hot10k", "mean_batch_topics.hot10k",
        "topic_cache_hit_share.hot10k", "device_answer_share.hot10k",
        "deadline_fallback_share.hot10k"}


def test_untraced_line_has_the_end_to_end_metrics():
    line, failures = rehearse(CELL, 0)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("end_to_end")
    assert line["attempted"] > 1000 and line["failed"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_line_has_every_per_layer_metric():
    line, failures = rehearse(CELL, 1)
    assert failures == "['platform is cpu, not tpu']"
    # (the kernel's metrics need the chip's trace; none is this cell's)
    assert set(line["metrics"]) == names("per_layer")
    assert "window_s" in line["device"] and "breakdown" in line
    # the Zipf head and the live hits stay in the cache
    assert line["metrics"]["topic_cache_hit_share.hot10k"]["value"] > 50
    # what the ledger knows to cost the flood 8-24% (batches sent to the
    # device on a low round-trip estimate, the deadline) reads in the line;
    # a traced rehearsal on the CPU does run into the deadline
    for share in ("device_answer_share", "deadline_fallback_share"):
        assert 0 <= line["metrics"][share + ".hot10k"]["value"] <= 100
