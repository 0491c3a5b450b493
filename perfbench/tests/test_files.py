"""BENCHMARK.json and the files it names agree, the generators are the
seed's, and the harness knows no cell, configuration or metric by name."""

import json
import os
import re

import endtoend
import generators
import readers
import roofline

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_every_name_has_its_file():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    for cell in b["workloads"]:
        conf = configs[cell["config"]]
        with open(os.path.join(ROOT, conf["file"])) as fh:
            config = json.load(fh)
        assert callable(generators.find(config["table"]["recipe"]))
        assert callable(generators.find(config["live"]["recipe"]))
        # nothing in the overrides chooses a matcher path
        assert not [k for k in config["broker"] if "matcher" in k]
        path = os.path.join(BENCH, "traffic", cell["traffic"] + ".json")
        with open(path) as fh:
            traffic = json.load(fh)
        assert traffic["loop"] in ("open", "closed")
        assert ("rate" if traffic["loop"] == "open" else "in_flight") \
            in traffic
        for t in traffic["topics"]:
            assert callable(generators.find(t["recipe"]))
        assert os.path.exists(os.path.join(
            BENCH, "rehearse", cell["config"] + ".json"))
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"]:
        assert callable(getattr(endtoend, m["name"]))
    for m in b["per_layer"]:
        with open(os.path.join(BENCH, "layers", m["name"] + ".json")) as fh:
            layer = json.load(fh)
        assert callable(getattr(readers, layer["reader"]))
        assert (layer["layer"], layer["moves"], layer["unit"]) == \
            (m["layer"], m["moves"], m["unit"])
        assert m["moves"] in e2e


def test_each_metric_moves_one_its_cells_report():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    reported = {c: {m["name"] for m in b["end_to_end"]
                    if c in m.get("workloads", cells)} for c in cells}
    for m in b["per_layer"]:
        for c in m.get("workloads", cells):
            assert m["moves"] in reported[c], (m["name"], c)
    for c in cells:
        assert "setup_s" in reported[c] and len(reported[c]) >= 2


def test_harness_names_no_cell_config_or_metric():
    b = bench()
    names = ([w["name"] for w in b["workloads"]]
             + [c["name"] for c in b["configs"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    for script in ("run.py", "loadgen.py", "sweep.py"):
        with open(os.path.join(BENCH, script)) as fh:
            code = fh.read().split('"""', 2)[2]      # past the docstring
        for name in names:
            assert not re.search(r"[\"']" + re.escape(name) + r"[\"']", code), \
                (script, name)


def test_same_seed_same_inputs():
    seed = 3_000_000_011        # more than 32 signed bits hold
    assert generators.corpus(500, seed) == generators.corpus(500, seed)
    assert generators.corpus(500, seed) != generators.corpus(500, seed + 1)
    assert generators.live_plan(seed) == generators.live_plan(seed)
    plan, groups, hits = generators.live_plan(seed)
    assert len(plan) == 64 and sum(len(v) for v in plan.values()) == 160
    assert sorted(len(m) for m in groups.values()) == [4, 4, 4, 4]
    filters = generators.corpus(20_000, seed)
    share = sum(f.startswith("$share/") for f in filters) / len(filters)
    plus = sum("+" in f for f in filters) / len(filters)
    hashes = sum(f.endswith("#") for f in filters) / len(filters)
    assert abs(share - 0.1) < 0.01 and abs(plus - 0.3) < 0.02
    assert abs(hashes - 0.15) < 0.02


def test_kernel_bytes_by_hand():
    # PR 22's plan at 1M filters: three chunks of 2048 words, one-hot
    # expansion; every number below is 4-byte words
    plan = {"g_pad": 56, "groups32": 50, "groups16": 0, "n_chunks": 3,
            "n_chunks32": 3, "chunk32": 2048, "n_chunks16": 0, "chunk16": 0}
    per_call = (2048 * 56 + 2048 * 32
                + 16 * (2 * 56 + 1 + 1 + 7)) * 4
    assert roofline.kernel_call_bytes({"plan": plan, "max_rows": 7}) \
        == per_call
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    try:
        roofline.peak("TPU v9", "hbm_bytes_per_s")
    except KeyError:
        pass
    else:
        raise AssertionError("a device not in the table must be an error")
