"""The trace reduction: on planes made by hand, and on a small trace
recorded on the chip (``testdata/record.py``) whose numbers were read
off it by hand."""

import os
from types import SimpleNamespace as NS

import pytest

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(os.path.dirname(HERE), "testdata", "small.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_reduce_by_hand():
    data = NS(planes=[
        plane("/device:TPU:0",
              XLA_Modules=[ev("jit_f", 0, 1_000)],       # nests the ops:
              XLA_Ops=[ev("fusion.1", 100, 200),         # not counted
                       ev("kernel", 250, 100),           # overlaps 50
                       ev("kernel", 1_000, 100),
                       ev("copy", 5_000, 50)]),
        plane("/device:TPU:1",
              XLA_Ops=[ev("kernel", 0, 400)]),
        plane("/device:TPU:0 SparseCore 0", XLA_Ops=[ev("x", 0, 10**9)]),
        plane("/host:CPU", python=[ev("wait", 0, 10**9)]),
    ])
    out = xplane.reduce_data(data, 1e-5, chips=2)
    assert out["planes"] == ["/device:TPU:0", "/device:TPU:1"]
    # chip 0: [100,350) + [1000,1100) + [5000,5050) = 400; chip 1: 400
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["ops"]["kernel"] == (3, pytest.approx(600e-9))
    assert out["ops"]["fusion.1"] == (1, pytest.approx(200e-9))
    assert "jit_f" not in out["ops"] and "x" not in out["ops"]
    # the longest gaps on chip 0: 1100 -> 5000, then 350 -> 1000
    assert out["gaps"][0] == (1_100, pytest.approx(3_900e-9))
    assert out["gaps"][1] == (350, pytest.approx(650e-9))


def test_no_device_plane_reads_nothing():
    out = xplane.reduce_data(NS(planes=[plane("/host:CPU", t=[])]), 1.0, 1)
    assert out["busy_s"] == 0 and out["ops"] == {} and out["gaps"] == []


def test_recorded_trace_read_by_hand():
    """Five runs of one jitted program with 10 ms pauses, on a TPU v5
    lite (testdata/record.py). Read off ``python perfbench/xplane.py``:
    plane '/device:TPU:0', line 'XLA Ops': 5 x copy-start (66 ns),
    5 x copy-done (12 ns), 5 x fusion (9.124 us); the modules nest them
    and are not counted; four pauses of 10.7-11.9 ms between the runs."""
    out = xplane.reduce(SMALL, 0.06, chips=1)
    assert out["planes"] == ["/device:TPU:0"]
    assert sorted(out["ops"]) == ["copy-done", "copy-start", "fusion"]
    assert out["ops"]["fusion"] == (5, pytest.approx(9.124e-6, rel=1e-6))
    assert out["ops"]["copy-start"][0] == 5
    assert out["busy_s"] == pytest.approx(9.202e-6, rel=1e-6)
    long = [s for _t, s in out["gaps"] if s > 1e-3]
    assert len(long) == 4 and all(0.0107 < s < 0.0119 for s in long)
    assert xplane.short_name(
        "%tpu_custom_call.4 = u32[128,8]{1,0} custom-call(u32[128,16] %p)"
    ) == "tpu_custom_call.4"
