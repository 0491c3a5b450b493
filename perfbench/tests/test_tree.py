"""The cell ``tree-100k.lockstep``: ``BASELINE.json`` ``configs[2]``'s
five-level ``+``/``#`` wildcard tree of 100,000 stored subscriptions,
watched by 400 live sessions whose filters nest, under 256 device
connections with one QoS 1 message each in flight. Its files say what
ISSUE 39 set, its three recipes are deterministic in ``--seed`` and give
the deliveries a message the configuration states whatever the seed, it
rehearses on the CPU to a line with every metric of its own, and the
planted faults show."""

import json
import os
import random
import re
import subprocess
import sys

import numpy as np
import pytest

import generators
import reference
from rehearsal import BENCH, bench, rehearse

CELL = "tree-100k.lockstep"
PER_LAYER = {
    "engine_host_answer_ms.tree", "engine_prep_us.tree",
    "engine_probe_us.tree", "engine_decode_us.tree", "device_rtt_us.tree",
    "device_answer_share.tree", "mean_batch_topics.tree",
    "topic_cache_hit_share.tree", "host_probe_share.tree",
    "stage_match_queue_ms.tree", "settle_hop_ms.tree",
    "deadline_fallback_share.tree", "stage_resolve_us.tree",
    "stage_fanout_us.tree", "stage_flush_ms.tree", "stage_drain_ms.tree",
    "stage_pipeline_wait_ms.tree", "stage_ack_us.tree",
    "stage_decode_us.tree", "loop_lag_ms.tree", "gen_cpu_share.tree",
    "kernel_us_per_call.tree", "sig_match_roofline.tree"}
# read from the device's trace: nothing to read on the CPU
DEVICE_ONLY = {"kernel_us_per_call.tree", "sig_match_roofline.tree"}
LOOP_RATE = {f"loop_{s}_us.rate" for s in (
    "busy", "read", "deliver", "pass", "flush", "ack", "other", "offcpu")}
SEED = 3_000_000_011            # more than 32 signed bits hold
TOPIC_CACHE = 8192              # VersionedTopicCache's default size


def names(kind: str) -> set:
    return {m["name"] for m in bench()[kind]
            if CELL in m.get("workloads", [CELL])}


def load(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def test_cell_config_and_traffic_are_what_the_issue_set():
    b = bench()
    assert [len(b[k]) for k in ("configs", "workloads")] == [5, 7]
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 2
    cell = b["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "tree-100k", "tree-lockstep", 1)
    t = load("traffic", "tree-lockstep.json")
    assert set(t) == set(load("traffic", "fanin-flood.json"))
    # fleet-1m's telemetry payloads on the fan-in's lockstep
    assert (t["loop"], t["in_flight"], t["qos1_share"], t["payload_bytes"],
            t["drain_grace_s"]) == ("closed", 1, 1, [64, 512], 20)
    assert t["payload_bytes"] == \
        load("traffic", "fleet-flood.json")["payload_bytes"]
    assert t["publishers"] in (256, 128)    # the one parameter that may move
    assert t["topics"] == [{"recipe": "tree_topics", "share": 1.0}]
    conf = b["configs"][-1]
    config = load("configs", "tree-100k.json")
    assert config["broker"] == load("configs", "fleet-1m.json")["broker"]
    assert (config["table"]["recipe"], config["table"]["subscriptions"]) \
        == ("tree_table", 100000)       # the source's: not cut
    assert config["live"]["recipe"] == "tree_live"
    assert "args" not in config["table"] and "args" not in config["live"]
    assert [r.split(":")[0] for r in config["reduced"]] == conf["reduced"] \
        == ["session_records", "retained_messages", "worker_pool"]
    assert {"source", "deployment", "chips", "guarantees", "assumed"} \
        <= set(config)
    assert {"branching", "filter shares", "filter depth", "stored clients",
            "live population", "QoS of the watchers"} <= set(config["assumed"])
    assert any("one copy a session a message" in g and "highest" in g
               for g in config["guarantees"])
    for source in (conf["source"], config["source"]):
        assert "BASELINE.json configs[2]" in source
    assert max(map(len, (conf["source"], conf["why"], cell["why"]))) <= 200
    assert names("end_to_end") == {"delivered_rate", "setup_s"}
    assert {n for n in names("per_layer") if n.endswith(".tree")} \
        == PER_LAYER
    assert LOOP_RATE <= names("per_layer")
    by_name = {m["name"]: m for m in b["per_layer"]}
    for n in PER_LAYER:
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["moves"] == "delivered_rate"
        layer = load("layers", n + ".json")
        assert (layer["moves"], layer["unit"], layer["layer"]) == (
            "delivered_rate", by_name[n]["unit"], by_name[n]["layer"])
    for n in LOOP_RATE | {"delivered_rate"}:
        entry = by_name.get(n) or b["end_to_end"][0]
        assert entry["workloads"][-1] == CELL
    # here the host probe's share is what the cell exists for
    assert by_name["host_probe_share.tree"]["better"] == "higher"
    assert by_name["mean_batch_topics.tree"]["better"] == "higher"
    assert load("layers", "stage_resolve_us.tree.json")["args"] == \
        {"stage": "resolve"}
    small = load("rehearse", "tree-100k.json")
    assert small["table"] == {"recipe": "tree_table", "subscriptions": 5000}
    assert small["live"] == {"recipe": "tree_live", "args": {"scale": 0.1}}


def test_tree_table_is_deterministic_and_the_mix_the_file_states():
    table = generators.find("tree_table")
    filters = table(20_000, SEED)
    assert filters == table(20_000, SEED) != table(20_000, SEED + 1)
    assert filters[:500] == table(500, SEED)
    n = len(filters)
    levels = [f.split("/") for f in filters]
    cut = [lv for lv in levels if lv[-1] == "#"]
    shares = (sum("+" not in lv and lv[-1] != "#" for lv in levels) / n,
              sum(lv.count("+") == 1 for lv in levels) / n,
              sum(lv.count("+") == 2 for lv in levels) / n, len(cut) / n)
    assert all(abs(got - want) < 0.015 for got, want in
               zip(shares, (0.30, 0.25, 0.15, 0.30)))
    assert not any(f.startswith("$share/") for f in filters)
    # a '#' filter keeps 1-4 levels with weights 1, 2, 3, 4; every other
    # filter names all five: 2 to 5 levels deep
    depth = [sum(len(lv) == d + 1 for lv in cut) / len(cut)
             for d in (1, 2, 3, 4)]
    assert all(abs(share - w / 10) < 0.03
               for share, w in zip(depth, (1, 2, 3, 4)))
    assert {len(lv) for lv in levels} == {2, 3, 4, 5}
    assert all(len(lv) == 5 for lv in levels if lv[-1] != "#")
    assert {lv.count("+") for lv in levels} == {0, 1, 2}
    for lv in levels:
        for at, name in enumerate(lv):
            assert name in ("+", "#") or (name[0] == "abcde"[at]
                                          and name[1:] in "0123456789")


def test_tree_topics_are_seeded_leaves_ten_times_the_topic_cache():
    from recipes.tree_table import LEAVES
    assert LEAVES == 100_000 >= 10 * TOPIC_CACHE
    from maxmq_tpu.matching.trie import VersionedTopicCache
    assert VersionedTopicCache().maxsize == TOPIC_CACHE
    draw = generators.find("tree_topics")(SEED, [])
    topics = [draw(random.Random(SEED)) for _ in range(2)]
    assert topics[0] == topics[1]
    rng = random.Random(SEED)
    drawn = [draw(rng) for _ in range(20_000)]
    assert all(re.fullmatch(r"a\d/b\d/c\d/d\d/e\d", t) for t in drawn)
    # uniform: no hot head (the commonest leaf of 20,000 draws comes a
    # handful of times), every first level about a tenth
    assert len(set(drawn)) > 17_500
    tenth = [sum(t.startswith(f"a{i}/") for t in drawn) for i in range(10)]
    assert max(tenth) - min(tenth) < 400
    # a leaf is matched by some hundreds of stored entries at full size
    filters = generators.find("tree_table")(20_000, SEED)
    assert sum(reference.matches(f, drawn[0]) for f in filters) > 40


def leaf_masks():
    """mask(filter) -> bool[100,000] over every leaf, by the plain rule
    and numpy alone: level ``at`` of leaf ``n`` is digit ``at`` of n."""
    leaves = np.arange(10 ** 5)
    digit = [leaves // 10 ** (4 - at) % 10 for at in range(5)]

    def mask(filt: str) -> np.ndarray:
        m = np.ones(10 ** 5, dtype=bool)
        levels = filt.split("/")
        for at, name in enumerate(levels):
            if name == "#":
                return m
            if name != "+":
                assert name[0] == "abcde"[at]
                m &= digit[at] == int(name[1:])
        assert len(levels) == 5
        return m
    return mask


def test_tree_live_reaches_the_same_sessions_a_message_for_ten_seeds():
    """Counted over every one of the 100,000 leaves, for ten seeds: the
    mean receivers a topic is the configuration's 7.6 within 1% (it is
    exact), 6-9 as ISSUE 39 asks; a topic matches more live rows than it
    has receivers; a fifth of the deliveries reach a session through
    several of its filters, some at different QoS; a third are QoS 1."""
    live = generators.find("tree_live")
    mask = leaf_masks()
    from recipes.tree_live import RECEIVERS
    assert RECEIVERS == 7.6
    for seed in range(SEED, SEED + 10):
        plan, groups, hits = live(seed)
        assert (plan, groups, hits) == live(seed) and groups == {}
        assert len(plan) == 400
        assert sum(len(v) for v in plan.values()) == 1470 < 2000
        receivers = np.zeros(10 ** 5, dtype=int)
        rows, folded, differ, qos1 = (receivers.copy() for _ in range(4))
        for subs in plan.values():
            hit = [(mask(f), q) for f, q in subs]
            count = sum(m.astype(int) for m, _q in hit)
            high = np.max([m * q for m, q in hit], axis=0)
            low = np.min([np.where(m, q, 1) for m, q in hit], axis=0)
            receivers += count > 0
            rows += count
            folded += count > 1
            differ += (count > 1) & (high != low)
            qos1 += (count > 0) & (high == 1)
            assert count.max() <= 3
        mean = receivers.mean()
        assert abs(mean / RECEIVERS - 1) < 0.01 and 6 <= mean <= 9
        assert 6 <= receivers.min() and receivers.max() <= 10
        assert rows.mean() > mean + 1.5
        assert 0.20 < folded.mean() / mean < 0.25
        assert 0.02 < differ.mean() / mean < 0.05
        assert 0.30 < qos1.mean() / mean < 0.36
    assert plan != live(SEED)[0]
    kinds: dict = {}
    for cid in plan:
        kinds[cid.split("-")[1]] = kinds.get(cid.split("-")[1], 0) + 1
    assert kinds == {"site": 20, "area": 100, "kind": 100, "point": 30,
                     "cross": 50, "cell": 100}
    # a quarter of the watchers at QoS 1 throughout, an eighth at both
    # (every fourth and every eighth of each kind, rounded up)
    grants = [{q for _f, q in subs} for subs in plan.values()]
    assert sum(g == {1} for g in grants) == 101
    assert sum(g == {0, 1} for g in grants) == 53
    assert not any(f.startswith("$share/")
                   for subs in plan.values() for f, _q in subs)
    # the reference agrees with the count on a sample, and grants the
    # highest QoS of a session's matching filters
    ref = reference.Reference(plan)
    draw = generators.find("tree_topics")(seed, hits)
    rng = random.Random(7)
    for _ in range(300):
        topic = draw(rng)
        plain, shared = ref.receivers(topic)
        assert shared == {}
        at = int("".join(name[1:] for name in topic.split("/")))
        assert len(plain) == receivers[at]
        for cid, granted in plain.items():
            assert granted == max(q for f, q in plan[cid]
                                  if reference.matches(f, topic))
    # the rehearsal's tenth keeps the shapes
    small, _g, _h = live(SEED, scale=0.1)
    assert len(small) == 40 and sum(len(v) for v in small.values()) == 147


def test_untraced_line_has_the_end_to_end_metrics():
    line, failures = rehearse(CELL, 0)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("end_to_end")
    assert line["attempted"] > 2000 and line["failed"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_line_has_every_per_layer_metric():
    line, failures = rehearse(CELL, 1)
    assert failures == "['platform is cpu, not tpu']"
    assert set(line["metrics"]) == names("per_layer") - DEVICE_ONLY
    assert "window_s" in line["device"] and "breakdown" in line
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the resolve is a part of the fan-out it lies in, and the decode
    # of the inline answer it is a phase of
    assert 0 < m["stage_resolve_us.tree"] <= m["stage_fanout_us.tree"]
    assert 0 < m["engine_decode_us.tree"] <= \
        1000 * m["engine_host_answer_ms.tree"]
    assert m["engine_prep_us.tree"] > 0 and m["engine_probe_us.tree"] > 0
    assert m["stage_drain_ms.tree"] > 0 and m["stage_flush_ms.tree"] > 0
    assert m["stage_ack_us.tree"] > 0 and m["stage_decode_us.tree"] > 0
    # 5,000 leaves fit the topic cache whole, so the rehearsal's hit
    # share says nothing of the cell's; who answers the rest does
    for share in ("device_answer_share", "deadline_fallback_share",
                  "topic_cache_hit_share", "host_probe_share",
                  "gen_cpu_share"):
        assert 0 <= m[share + ".tree"] <= 100
    assert m["host_probe_share.tree"] + m["device_answer_share.tree"] > 50
    assert all(m[n] > 0 for n in LOOP_RATE - {"loop_offcpu_us.rate"})


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


@pytest.mark.parametrize("fault", ["overlap_twice", "overlap_low"])
def test_a_broken_overlap_rule_shows_as_a_wrong_set(fault):
    """The guarantee the deployment states, broken: a second copy to a
    session matched twice, or its one copy at the lowest QoS."""
    line, failures = broken("faults_overlap.py", fault)
    assert line["correct"] is False
    assert "delivered to a wrong set" in failures
    if fault == "overlap_twice":
        # everything came, and once too often
        assert "never arrived" not in failures and line["failed"] == 0
    else:
        assert "order broken" not in failures


def test_drop_shows_as_failed_operations_and_lost_qos1_deliveries():
    line, failures = broken("faults.py", "drop")
    assert line["correct"] is False
    assert "QoS 1 deliveries never arrived" in failures
    assert 0.1 < line["failed"] / line["attempted"] < 0.6


def test_stranger_shows_in_a_cell_of_plain_pairs():
    line, failures = broken("faults.py", "stranger")
    assert line["correct"] is False
    assert "delivered to a wrong set" in failures
