"""The cell ``sparkplug-plant.steady``: its files are the ones it was
proven on, it rehearses on the CPU to a line with every metric of its
own, the planted faults show in it, and every open-loop cell keeps a
``gen_late_*`` metric of its own."""

import json
import os

import pytest

from rehearsal import BENCH, bench, rehearse

CELL = "sparkplug-plant.steady"
PER_LAYER = {
    "gen_late_p50_ms", "gen_late_max_ms", "stage_match_queue_ms",
    "stage_match_answer_ms", "engine_host_answer_ms",
    "stage_pipeline_wait_ms", "stage_fanout_ms", "stage_drain_ms",
    "loop_lag_ms", "mean_batch_topics", "device_answer_share",
    "topic_cache_hit_share", "host_probe_share"}


def names(kind: str) -> set:
    return {m["name"] for m in bench()[kind]
            if CELL in m.get("workloads", [CELL])}


def traffic_of(cell: dict) -> dict:
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as fh:
        return json.load(fh)


def test_cell_and_traffic_are_what_was_proven():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    # a whole host (one chip used) for steadiness alone, as every
    # open-loop cell judged on a latency
    assert (cell["config"], cell["chips"]) == ("sparkplug-plant", 4)
    assert "steadiness" in cell["why"]
    t = traffic_of(cell)
    assert (t["loop"], t["publishers"], t["qos1_share"], t["payload_bytes"],
            t["drain_grace_s"]) == ("open", 16, 0, [48, 400], 20)
    assert [(x["recipe"], x["share"]) for x in t["topics"]] == \
        [("sparkplug_topics", 1)]
    # 0.8 x the sweep's knee, rounded down to a multiple of 100
    assert t["rate"] % 100 == 0 and 500 <= t["rate"] <= 4000
    assert "0.8 x" in t["why"]
    conf = next(c for c in b["configs"] if c["name"] == "sparkplug-plant")
    with open(os.path.join(os.path.dirname(BENCH), conf["file"])) as fh:
        config = json.load(fh)
    assert [r.split(":")[0] for r in config["reduced"]] == conf["reduced"]
    # the plant's size is said once per file, and the files agree
    plant = config["table"]["args"]
    assert config["live"]["args"] == plant
    assert {k: t["topics"][0]["args"][k] for k in plant} == plant
    assert sorted(config["broker"]) == [
        "log_level", "metrics_address", "mqtt_tcp_address",
        "storage_backend"]
    assert {"source", "deployment", "guarantees", "assumed"} <= set(config)
    assert names("end_to_end") == {"deliver_p50_ms", "setup_s"}
    assert {n.rsplit(".", 1)[0] for n in names("per_layer")
            if n.endswith(".sparkplug")} == PER_LAYER
    # the labels say what runs: the trie answers inline and the cost
    # model rightly picks it, so this is no matcher cell
    by_name = {m["name"]: m for m in b["per_layer"]}
    assert by_name["stage_match_answer_ms.sparkplug"]["layer"] == "trie index"
    assert by_name["engine_host_answer_ms.sparkplug"]["layer"] == "trie index"
    assert (by_name["host_probe_share.sparkplug"]["layer"],
            by_name["host_probe_share.sparkplug"]["better"]) == \
        ("micro-batcher", "lower")
    assert "trie" in cell["why"] and "matcher host half" not in cell["why"]
    assert "ISSUE 28's choice" in conf["source"]
    # the contract's limit on a line of BENCHMARK.json
    assert max(map(len, (conf["source"], conf["why"], cell["why"]))) <= 200


def test_every_open_loop_cell_has_a_gen_late_metric_of_its_own():
    b = bench()
    for cell in b["workloads"]:
        if traffic_of(cell)["loop"] != "open":
            continue
        own = [m["name"] for m in b["per_layer"]
               if m["name"].startswith("gen_late_")
               and m.get("workloads") == [cell["name"]]]
        assert own, cell["name"]


def test_untraced_line_has_the_end_to_end_metrics():
    line, failures = rehearse(CELL, 0)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("end_to_end")
    # three host-side receivers a message, all QoS 0: no PUBACK is due
    assert line["attempted"] > 3000 and line["failed"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_line_has_every_per_layer_metric():
    line, failures = rehearse(CELL, 1)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("per_layer")
    assert "window_s" in line["device"] and "breakdown" in line
    # 110,000 topics against a cache of 8,192: few repeat in a rehearsal
    assert line["metrics"]["topic_cache_hit_share.sparkplug"]["value"] < 50


@pytest.mark.parametrize("fault, says", [
    ("stranger", "delivered to a wrong set"),
    ("drop", None),         # QoS 0: what is left out is a failed operation
])
def test_a_broken_run_shows(fault, says):
    line, failures = rehearse(CELL, 0, fault=fault)
    assert line["correct"] is False
    if says:
        assert says in failures
    else:
        assert 0.4 < line["failed"] / line["attempted"] < 0.6
