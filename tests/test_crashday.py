"""ADR 024: crashday kill-point harness — tier-1 lanes.

The full day is 20 kills per policy (``python -m harness.crashday``);
this lane proves the harness itself stays healthy in under a minute:

* the ``--smoke`` shape end to end — real subprocess brokers, crash
  points armed through the MAXMQ_FAULTS rail, the SLO sheet scored —
  asserting zero PUBACKed loss under ``always`` plus all four degrade
  /torn-tail contracts;
* the ``batched`` loss-window contract in isolation: crash inside an
  open commit window, measure what the acked ledger lost, assert the
  window bound AND the FIFO-suffix shape of the loss.
"""

import asyncio
import json
import os
import signal

import pytest

from harness.crashday import KILL_POINTS, CrashDay
from maxmq_tpu import faults


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


async def test_crashday_smoke_slo_sheet_passes(tmp_path):
    day = CrashDay(policy="always", smoke=True,
                   store_dir=str(tmp_path))
    sheet = await day.run()
    assert sheet["pass"], f"SLO violations: {sheet['violations']}"
    assert sheet["pubacked_loss"] == 0
    assert sheet["acked_total"] > 0
    assert sheet["qos2_duplicates"] == 0
    assert sheet.get("session_losses", 0) == 0
    # the smoke's 3 kills all armed real crash points
    assert sum(sheet["kill_points"].values()) == 3
    assert set(sheet["kill_points"]) <= set(KILL_POINTS)
    # every phase ran
    assert [p["name"] for p in sheet["phases"]] == \
        ["kill_cycles", "torn_tail", "enospc", "fsync"]
    # torn tail: serving boot + exact quarantine accounting
    assert sheet["torn"]["boot_serving"]
    assert sheet["torn"]["quarantined"] == sheet["torn"]["planted"] == 4
    # degrade phases degraded instead of wedging
    assert sheet["enospc"]["alive"] and sheet["fsync"]["alive"]
    assert sheet["enospc"]["enospc_failures"] >= 1
    assert sheet["enospc"]["journal_sheds"] >= 1
    assert sheet["fsync"]["backend_reopens"] >= 1
    assert sheet["fsync"]["breaker_recoveries"] >= 1
    # recovery SLO fields present for the bench row
    assert sheet["recovery_p99_ms"] <= day.slo_recovery_ms
    # the sheet IS the bench row: it must survive the JSON round trip
    json.loads(json.dumps(sheet))

test_crashday_smoke_slo_sheet_passes._async_timeout = 120


async def test_batched_crash_mid_window_loss_bounded(tmp_path):
    """Satellite (ADR 024): under ``storage_sync=batched`` a crash
    inside an open commit window loses exactly the acked tail that
    window held — bounded by batch_ops + the offered traffic of ~3
    windows, and shaped as a FIFO suffix of the ack sequence (group
    commit never reorders a durability promise)."""
    day = CrashDay(policy="batched", msgs_per_cycle=24, batch_ms=700,
                   batch_ops=512, store_dir=str(tmp_path), seed=24)
    db = os.path.join(day.dir, "w.db")
    try:
        # boot 1: durable subscriber, fully settled (its session must
        # COMMIT — a lost session would hide the loss we measure)
        proc = day._spawn(db)
        assert await day._wait_ready_or_death(proc)
        await day._setup_subscriber()
        await asyncio.sleep(day._settle_s())
        day._kill(proc)
        # two crash cycles: ack a burst well inside one 700ms window,
        # SIGKILL with zero grace — the acked tail dies uncommitted
        for cycle in (1, 2):
            proc = day._spawn(db)
            assert await day._wait_ready_or_death(proc)
            acked = await day._stream_until_death(proc, cycle)
            assert acked == day.msgs_per_cycle
            day._kill(proc)
        # clean boot: drain everything the store still owes
        proc = day._spawn(db)
        assert await day._wait_ready_or_death(proc)
        await day._drain()
        await asyncio.sleep(day._settle_s())
        day._kill(proc)
    finally:
        for p in day._procs:
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)
                p.wait(timeout=5)
    day._score()
    s = day.sheet
    assert s["pass"], f"SLO violations: {s['violations']}"
    # the kill landed mid-window: some PUBACKed messages genuinely
    # died (this is the measured window, not a zero-loss claim) ...
    assert s["pubacked_loss"] > 0
    # ... every one inside its cycle's declared bound ...
    for cycle, n in s["batched_loss_by_cycle"].items():
        assert n <= s["batched_loss_bounds"][cycle]
    # ... and QoS2 stayed exactly-once even across the lossy window
    assert s["qos2_duplicates"] == 0

test_batched_crash_mid_window_loss_bounded._async_timeout = 120
