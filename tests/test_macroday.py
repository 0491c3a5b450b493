"""ADR 020: macroday composed-fault harness smoke.

One tiny-knob production day end to end — the full phase ladder
(storm, fan-in/out, shed, churn, partition+heal, node kill) on a live
3-node mesh with ``cluster_fwd_durability=chained`` — scored against
the SLO sheet. This lane proves the scheduler, the fault arming, and
the scoring stay healthy in under a minute (it also runs under the
asyncio-debug CI lane, so a leaked task or un-retrieved future fails
here first).
"""

import json

import pytest

from harness import MacroDay
from maxmq_tpu import faults


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


async def test_macroday_smoke_slo_sheet_passes():
    day = MacroDay(storm_clients=9, telemetry_msgs=6, command_msgs=5,
                   cut_msgs=6, parked_msgs=8, keepalive=0.5,
                   will_grace=1.0, settle_s=10.0)
    sheet = await day.run()
    assert sheet["pass"], f"SLO violations: {sheet['violations']}"
    assert sheet["pubacked_loss"] == 0
    assert sheet["pubacked_total"] > 0
    assert sheet["wills_fired"] == 1
    assert sheet["wills_delivered"] == 1
    assert sheet["takeover_session_present"]
    assert sheet["takeover_recovery_ms"] >= 0
    assert sheet["heal_convergence_ms"] >= 0
    assert sheet["shed_entered"] and sheet["shed_recovered"]
    assert sheet["relay_chain_waits"] >= 1
    # every phase ran, in order, and the fault-arming ones recorded
    # their sites (the replayability contract: armed_sites + fired
    # deltas make a failing day reproducible phase by phase)
    names = [p["name"] for p in sheet["phases"]]
    assert names == ["connect_storm", "fanin_fanout", "slow_consumer",
                     "sub_churn", "partition_heal", "node_kill"]
    by_name = {p["name"]: p for p in sheet["phases"]}
    assert by_name["slow_consumer"]["armed_sites"]
    assert by_name["partition_heal"]["armed_sites"]
    assert any(p["fired"] for p in sheet["phases"])
    # the sheet IS the bench row: it must survive the JSON round trip
    json.loads(json.dumps(sheet))
    # nothing left armed for the next test
    assert not faults.REGISTRY.any_armed()


async def test_macroday_sharded_box_same_slo_sheet():
    """ADR 021: the SAME day replays against a sharded box — the
    three roles become pool workers over unix bridge links (plus one
    extra mesh member at workers=4) and the kill phase scores as
    ``worker_kill`` through the unchanged scorer."""
    day = MacroDay(storm_clients=9, telemetry_msgs=6, command_msgs=5,
                   cut_msgs=6, parked_msgs=8, keepalive=0.5,
                   will_grace=1.0, settle_s=10.0, workers=4)
    sheet = await day.run()
    assert sheet["pass"], f"SLO violations: {sheet['violations']}"
    assert sheet["pubacked_loss"] == 0
    assert sheet["workers"] == 4 and sheet["nodes"] == 4
    assert sheet["takeover_session_present"]
    assert sheet["wills_fired"] == 1
    names = [p["name"] for p in sheet["phases"]]
    assert names[-1] == "worker_kill" and "node_kill" not in names
    # every link in the in-box mesh is a local (unix) one
    assert all(ln.local for n in ("A", "C")
               for ln in day.mgrs[n].links.values())

test_macroday_sharded_box_same_slo_sheet._async_timeout = 120
