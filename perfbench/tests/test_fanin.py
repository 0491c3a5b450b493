"""The cell ``fleet-fanin-500.flood``: fan-in from 1,000 publisher
sockets with one message each in flight to a ``$share`` group of 500, on
``fleet-1m``'s table. Its files say what ISSUE 34 set, its live
population is the same for every seed, it rehearses on the CPU to a line
with every metric of its own, and the planted faults that can show in it
do."""

import json
import os
import re
import subprocess
import sys

import generators
import reference
from rehearsal import BENCH, bench, rehearse

CELL = "fleet-fanin-500.flood"
PER_LAYER = {
    "stage_share_pick_us.fanin", "stage_decode_us.fanin",
    "stage_ack_us.fanin", "stage_pipeline_wait_ms.fanin",
    "stage_match_queue_ms.fanin", "engine_host_answer_ms.fanin",
    "mean_batch_topics.fanin", "topic_cache_hit_share.fanin",
    "device_answer_share.fanin", "deadline_fallback_share.fanin",
    "stage_fanout_us.fanin", "stage_flush_ms.fanin", "stage_drain_ms.fanin",
    "loop_lag_ms.fanin", "gen_cpu_share.fanin"}


def names(kind: str) -> set:
    return {m["name"] for m in bench()[kind]
            if CELL in m.get("workloads", [CELL])}


def load(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def test_cell_config_and_traffic_are_what_the_issue_set():
    b = bench()
    assert len(b["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 2
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("fleet-fanin-500", "fanin-flood", 1)
    t = load("traffic", "fanin-flood.json")
    assert set(t) == {"loop", "publishers", "in_flight", "qos1_share",
                      "payload_bytes", "topics", "drain_grace_s", "why"}
    assert (t["loop"], t["in_flight"], t["qos1_share"], t["payload_bytes"],
            t["drain_grace_s"]) == ("closed", 1, 1, [16, 16], 20)
    assert t["publishers"] in (1000, 500)   # the one parameter that may move
    assert t["topics"] == [{"recipe": "live_hits", "share": 1.0}]
    conf = next(c for c in b["configs"] if c["name"] == "fleet-fanin-500")
    config = load("configs", "fleet-fanin-500.json")
    fleet = load("configs", "fleet-1m.json")
    # fleet-1m's broker and table; a live population of its own
    assert config["broker"] == fleet["broker"]
    for key in ("recipe", "subscriptions", "args"):
        assert config["table"][key] == fleet["table"][key]
    assert config["live"]["recipe"] == "fanin_live"
    assert config["live"]["args"] == {"subscribers": 500, "devices": 50000}
    assert [r.split(":")[0] for r in config["reduced"]] == conf["reduced"] \
        == ["publisher_connections", "inbound_rate", "payload_bytes",
            "session_records", "retained_messages", "worker_pool"]
    assert {"source", "deployment", "chips", "guarantees", "assumed"} \
        <= set(config)
    assert any("exactly one member" in g for g in config["guarantees"])
    for source in (conf["source"], config["source"]):
        assert "fanin-50K-500-50K-50K" in source and "configs[3]" in source
    assert max(map(len, (conf["source"], conf["why"], cell["why"]))) <= 200
    assert names("end_to_end") == {"delivered_rate", "setup_s"}
    assert {n for n in names("per_layer") if n.endswith(".fanin")} \
        == PER_LAYER
    by_name = {m["name"]: m for m in b["per_layer"]}
    for n in PER_LAYER:
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["moves"] == "delivered_rate"
    assert by_name["mean_batch_topics.fanin"]["better"] == "higher"
    assert load("layers", "stage_share_pick_us.fanin.json")["args"] == \
        {"stage": "share_pick"}
    assert load("layers", "stage_ack_us.fanin.json")["args"] == \
        {"stage": "ack"}
    # the sandbox's size: the table as fleet-1m's rehearsal, a smaller group
    small = load("rehearse", "fleet-fanin-500.json")
    assert small["table"] == load("rehearse", "fleet-1m.json")["table"]
    assert small["live"]["recipe"] == "fanin_live"


def test_fanin_live_is_the_same_for_a_seed():
    seed = 3_000_000_011        # more than 32 signed bits hold
    plan, groups, hits = generators.find("fanin_live")(seed)
    assert (plan, groups, hits) == generators.find("fanin_live")(seed)
    assert (plan, groups, hits) == generators.find("fanin_live")(seed + 1)
    assert len(plan) == 500 and len(hits) == len(set(hits)) == 50000
    assert groups == {"ingest": [f"ingest-{i}" for i in range(500)]}
    assert all(subs == [("$share/ingest/fleet/telemetry/#", 1)]
               for subs in plan.values())
    assert hits[0] == "fleet/telemetry/dev-0" \
        and hits[-1] == "fleet/telemetry/dev-49999"
    # the reference names the whole group for a device topic and nobody
    # outside it, nobody for another topic, and no stored filter of the
    # table reaches a device topic
    ref = reference.Reference(plan)
    plain, shared = ref.receivers(hits[31337])
    assert plain == {} and shared == {"ingest": {cid: 1 for cid in plan}}
    assert ref.receivers("a0/b1/c2") == ({}, {})
    assert ref.receivers("fleet/broadcast/cmd-1") == ({}, {})
    stored = generators.corpus(20_000, seed)
    assert not [f for f in stored for t in (hits[0], hits[49999])
                if reference.matches(reference.split_share(f)[1], t)]
    small, g, h = generators.find("fanin_live")(seed, subscribers=50,
                                                devices=5000)
    assert len(small) == len(g["ingest"]) == 50 and len(h) == 5000


def test_untraced_line_has_the_end_to_end_metrics():
    line, failures = rehearse(CELL, 0)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("end_to_end")
    # one delivery and one PUBACK a message, every PUBLISH QoS 1
    assert line["attempted"] > 2000 and line["attempted"] % 2 == 0
    assert line["failed"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_line_has_every_per_layer_metric():
    line, failures = rehearse(CELL, 1)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("per_layer")
    assert "window_s" in line["device"] and "breakdown" in line
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the picks are a part of the fan-out they lie in
    assert 0 < m["stage_share_pick_us.fanin"] <= m["stage_fanout_us.fanin"]
    assert m["stage_ack_us.fanin"] > 0 and m["stage_decode_us.fanin"] > 0
    assert m["stage_drain_ms.fanin"] > 0 and m["stage_flush_ms.fanin"] > 0
    for share in ("device_answer_share", "deadline_fallback_share",
                  "topic_cache_hit_share", "gen_cpu_share"):
        assert 0 <= m[share + ".fanin"] <= 100


def broken(script: str, fault: str) -> tuple:
    """``rehearsal.rehearse`` with a fault planted by ``script``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), fault, "--workload",
         CELL, "--rehearse", "--seed", "3000000021", "--seconds", "3",
         "--trace", "0"], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=300).stdout
    said = re.search(r"failures: (\[.*\])$", out, re.M)
    assert said and f"FAULT PLANTED: {fault}" in out, out[-3000:]
    return json.loads(out.strip().splitlines()[-1]), said.group(1)


def test_share_twice_shows_as_a_group_served_twice():
    line, failures = broken("faults.py", "share_twice")
    assert line["correct"] is False
    assert "delivered to a wrong set" in failures
    assert line["failed"] == 0      # everything came, and once too often


def test_share_skip_shows_as_lost_qos1_deliveries():
    line, failures = broken("faults_share.py", "share_skip")
    assert line["correct"] is False
    assert "QoS 1 deliveries never arrived" in failures
    # every second pick of a cell whose every delivery is a pick: half
    # the deliveries, a quarter of deliveries + PUBACKs
    assert 0.2 < line["failed"] / line["attempted"] < 0.3


def test_stranger_and_drop_find_no_plain_pair_to_alter():
    """Both alter the plain ``pairs`` of a resolved match result, and
    every delivery of this cell is a ``$share`` pick: they plant nothing
    and the run stays right."""
    for fault in ("stranger", "drop"):
        line, failures = broken("faults.py", fault)
        assert failures == "['platform is cpu, not tpu']", fault
        assert line["failed"] == 0
