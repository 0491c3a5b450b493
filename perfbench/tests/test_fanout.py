"""The cell ``fleet-fanout-1k.flood``: a wide QoS 1 broadcast on
``fleet-1m``'s table. Its files say what ISSUE 32 set, its live
population is the seed's, it rehearses on the CPU to a line with every
metric of its own, and the planted faults show in it."""

import json
import os
import re
import subprocess
import sys

import generators
import reference
from rehearsal import BENCH, bench, rehearse

CELL = "fleet-fanout-1k.flood"
PER_LAYER = {
    "gen_cpu_share.fanout", "loop_lag_ms.fanout", "stage_fanout_ms.fanout",
    "stage_flush_ms.fanout", "stage_drain_ms.fanout",
    "mean_batch_topics.fanout", "topic_cache_hit_share.fanout",
    "device_answer_share.fanout", "deadline_fallback_share.fanout"}


def names(kind: str) -> set:
    return {m["name"] for m in bench()[kind]
            if CELL in m.get("workloads", [CELL])}


def load(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def test_cell_config_and_traffic_are_what_the_issue_set():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("fleet-fanout-1k", "fanout-flood", 1)
    t = load("traffic", "fanout-flood.json")
    assert (t["loop"], t["publishers"], t["qos1_share"], t["payload_bytes"],
            t["drain_grace_s"]) == ("closed", 5, 1, [16, 16], 20)
    assert t["in_flight"] in (4, 8)     # the one parameter that may move
    assert t["topics"] == [{"recipe": "live_hits", "share": 0.2},
                           {"recipe": "corpus_topics", "share": 0.8}]
    conf = next(c for c in b["configs"] if c["name"] == "fleet-fanout-1k")
    config = load("configs", "fleet-fanout-1k.json")
    fleet = load("configs", "fleet-1m.json")
    # fleet-1m's broker and table; a live population of its own
    assert config["broker"] == fleet["broker"]
    for key in ("recipe", "subscriptions", "args"):
        assert config["table"][key] == fleet["table"][key]
    assert config["live"]["recipe"] == "fanout_live"
    assert config["live"]["args"] == {"subscribers": 1000, "topics": 5}
    assert [r.split(":")[0] for r in config["reduced"]] == conf["reduced"] \
        == ["outbound_rate", "payload_bytes", "session_records",
            "retained_messages", "worker_pool"]
    assert {"source", "deployment", "chips", "guarantees", "assumed"} \
        <= set(config)
    for source in (conf["source"], config["source"]):
        assert "fanout-5-1000-5-250K" in source and "configs[3]" in source
    assert max(map(len, (conf["source"], conf["why"], cell["why"]))) <= 200
    assert names("end_to_end") == {"delivered_rate", "setup_s"}
    assert {n for n in names("per_layer") if n.endswith(".fanout")} \
        == PER_LAYER
    by_name = {m["name"]: m for m in b["per_layer"]}
    for n in PER_LAYER:
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["moves"] == "delivered_rate"
    assert load("layers", "stage_flush_ms.fanout.json")["args"] == \
        {"stage": "flush", "scale": 0.001}
    # the sandbox's size: the table as fleet-1m's rehearsal, fewer sessions
    small = load("rehearse", "fleet-fanout-1k.json")
    assert small["table"] == load("rehearse", "fleet-1m.json")["table"]
    assert small["live"]["recipe"] == "fanout_live"


def test_fanout_live_is_the_seeds():
    seed = 3_000_000_011        # more than 32 signed bits hold
    plan, groups, hits = generators.find("fanout_live")(seed)
    assert (plan, groups, hits) == generators.find("fanout_live")(seed + 1)
    assert len(plan) == 1000 and groups == {}
    assert sum(len(v) for v in plan.values()) == 5000
    assert hits == [f"fleet/broadcast/cmd-{k}" for k in range(5)]
    assert all(subs == [(t, 1) for t in hits] for subs in plan.values())
    assert sorted(plan)[0] == "bc-dev-0" and "bc-dev-999" in plan
    # the reference names the 1,000 sessions for a broadcast, nobody for
    # a fresh topic, and no stored filter of the table reaches a broadcast
    ref = reference.Reference(plan)
    plain, shared = ref.receivers(hits[3])
    assert plain == {cid: 1 for cid in plan} and shared == {}
    assert ref.receivers("a0/b1/c2") == ({}, {})
    stored = generators.corpus(20_000, seed)
    assert not [f for f in stored for t in hits
                if reference.matches(reference.split_share(f)[1], t)]
    small, _g, _h = generators.find("fanout_live")(seed, subscribers=100)
    assert len(small) == 100


def test_untraced_line_has_the_end_to_end_metrics():
    line, failures = rehearse(CELL, 0)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("end_to_end")
    # 100 sessions a broadcast at the rehearsal's size, every PUBLISH QoS 1
    assert line["attempted"] > 3000 and line["failed"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_line_has_every_per_layer_metric():
    line, failures = rehearse(CELL, 1)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("per_layer")
    assert "window_s" in line["device"] and "breakdown" in line
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the pass that writes a broadcast's hundred sockets takes longer
    # than a fan-out to nobody, which is what the median publish is
    assert m["stage_flush_ms.fanout"] > m["stage_fanout_ms.fanout"] > 0
    assert m["stage_drain_ms.fanout"] > 0
    for share in ("device_answer_share", "deadline_fallback_share",
                  "topic_cache_hit_share"):
        assert 0 <= m[share + ".fanout"] <= 100


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


def test_drop_shows_as_lost_qos1_deliveries():
    line, failures = broken("faults.py", "drop")
    assert line["correct"] is False
    assert "QoS 1 deliveries never arrived" in failures
    # every second delivery of every broadcast
    assert 0.4 < line["failed"] / line["attempted"] < 0.6


def test_stranger_finds_nobody_to_add_and_uninvited_shows():
    """Every session that holds a subscription receives every message
    that reaches anybody, so ``faults.py stranger`` has no stranger to
    name and the run stays right; ``faults_wide.py uninvited`` hands a
    message that reaches nobody to a live session, and the check says
    so."""
    line, failures = broken("faults.py", "stranger")
    assert failures == "['platform is cpu, not tpu']" and line["failed"] == 0
    line, failures = broken("faults_wide.py", "uninvited")
    assert line["correct"] is False
    assert "delivered to a wrong set" in failures
