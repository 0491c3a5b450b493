"""What an open-loop cell needs of ``BENCHMARK.json`` and its files: a
latency to be judged on, a layer file for every per-layer metric and no
layer file that nothing names, and the traffic the cell was proven on."""

import json
import os

from rehearsal import BENCH, ROOT, bench


def traffic_of(cell: dict) -> dict:
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as fh:
        return json.load(fh)


def test_open_loop_cells_are_judged_on_latency():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    open_cells = [w["name"] for w in b["workloads"]
                  if traffic_of(w)["loop"] == "open"]
    assert open_cells, "the benchmark has no open-loop cell"
    for c in open_cells:
        mine = {m["name"] for m in b["end_to_end"]
                if c in m.get("workloads", cells)}
        # in an open loop the delivered rate is the offered rate
        assert "delivered_rate" not in mine, c
        assert "deliver_p50_ms" in mine, c
        # the generator's lateness stands beside every latency
        late = [m for m in b["per_layer"] if m["name"].startswith(
            "gen_late_") and c in m.get("workloads", cells)]
        assert late, c


def test_layer_files_and_entries_pair_up():
    named = {m["name"] for m in bench()["per_layer"]}
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "layers"))
               if f.endswith(".json")}
    assert named <= on_disk, sorted(named - on_disk)
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        open_questions = fh.read().split("\n## 7.", 1)[1]
    # a file that no entry names waits for its cell, and PERF.md says so
    for name in sorted(on_disk - named):
        assert f"`{name}`" in open_questions, \
            f"layers/{name}.json is named neither in BENCHMARK.json " \
            "nor in PERF.md section 7"


def test_steady_traffic_is_the_traffic_that_was_proven():
    cell = next(w for w in bench()["workloads"]
                if w["name"] == "fleet-1m.steady")
    # a whole host (four chips, one used) for steadiness alone: on a
    # shared host the median's runs spread too widely to be admitted
    assert (cell["config"], cell["chips"]) == ("fleet-1m", 4)
    assert "steadiness" in cell["why"]
    t = traffic_of(cell)
    assert (t["loop"], t["rate"], t["publishers"], t["qos1_share"],
            t["payload_bytes"], t["drain_grace_s"]) == \
        ("open", 1000, 16, 0.5, [64, 512], 20)
    assert {x["recipe"]: x["share"] for x in t["topics"]} == \
        {"corpus_topics": 0.5, "live_hits": 0.5}
    assert bench()["run_seconds"] == 20


def test_bounds_are_within_the_contract():
    for m in bench()["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]


def test_windows_describe_times_deliveries_from_due():
    import windows
    t0 = 1_000_000_000
    sent = [(0, k, "a/b", 0, t0 + k * 1_000_000_000, t0 + k * 1_000_000_000
             + 50_000, {}, {}) for k in range(4)]
    got = {"live-s0": [(b"0:%d:%d" % (k, t0 + k * 1_000_000_000),
                        t0 + k * 1_000_000_000 + (k + 1) * 1_000_000, 0)
                       for k in range(4)]
           + [(b"0:99:5", t0, 0)]}      # a straggler nobody sent now
    d = windows.describe([{"sent": sent, "got": got}], t0,
                         t0 + 4_000_000_000)
    assert (d["messages"], d["deliveries"]) == (4, 4)
    assert d["p50_ms"] == 2.5 and d["slices_p50_ms"] == [1.5, 3.5]
    assert d["gen_late_p50_ms"] == 0.05
