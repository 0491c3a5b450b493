"""Subscription churn: the generator's SUBSCRIBE / UNSUBSCRIBE framing,
the must / may / must-not rule of ``check.join``, and a rehearsal of a
churn cell on the CPU through ``run.measure``, sound and with each of
``faults_churn.py``'s faults planted."""

import argparse
import asyncio
import json
import os
import random
import subprocess

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import check  # noqa: E402
import loadgen  # noqa: E402
from recipes.churn_filters import churn_filters  # noqa: E402
from reference import matches  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


class Wire:
    def __init__(self):
        self.out = bytearray()

    def write(self, data):
        self.out += data


def packets(stream: bytes) -> list:
    """(type, body) of every packet in ``stream``."""
    out, pos = [], 0
    while pos < len(stream):
        b0, i, rem, shift = stream[pos], pos + 1, 0, 0
        while True:
            d = stream[i]
            i += 1
            rem |= (d & 0x7F) << shift
            shift += 7
            if d < 0x80:
                break
        out.append((b0, bytes(stream[i:i + rem])))
        pos = i + rem
    return out


# -- the framing --------------------------------------------------------------


def test_subscribe_frames_survive_any_chunking():
    async def body():
        rng = random.Random(11)
        conn = loadgen.Conn("load-c3", clean=True)
        conn.transport = Wire()
        rows = [conn.change("sub" if k % 3 else "unsub",
                            "a/" * rng.randint(0, 60) + f"x{k}/+/#", k % 2)
                for k in range(400)]
        # what the broker reads: one filter a packet, its packet id, its QoS
        stream = bytearray(b"\x20\x02\x00\x00")     # CONNACK
        for (b0, pkt), row in zip(packets(bytes(conn.transport.out)), rows):
            pid = int.from_bytes(pkt[:2], "big")
            n = int.from_bytes(pkt[2:4], "big")
            assert pkt[4:4 + n].decode() == row[1]
            if row[3] == "sub":
                assert b0 == 0x82 and pkt[4 + n:] == bytes([row[2]])
                code = 0x80 if pid % 50 == 0 else row[2]
                stream += b"\x90\x03" + pkt[:2] + bytes([code])
            else:
                assert b0 == 0xA2 and len(pkt) == 4 + n
                stream += b"\xb0\x02" + pkt[:2]
            stream += loadgen.publish_packet(b"t/x", b"7:%d:5|" % pid, 0, 0)
        conn.transport = Wire()
        pos = 0
        while pos < len(stream):
            n = rng.choice((1, 2, 3, 5, 64, 1_000))
            conn.data_received(bytes(stream[pos:pos + n]))
            pos += n
        assert await conn.connack
        assert conn.changes == {}
        assert all(r[5] is not None and r[5] >= r[4] for r in rows)
        refused = [r[1] for k, r in enumerate(rows)
                   if r[3] == "sub" and (k + 1) % 50 == 0]
        assert conn.refused == refused and refused
        assert len(conn.got) == len(rows)
        assert conn.transport.out == bytearray()    # no PUBACK for QoS 0
    asyncio.run(body())


def test_schedule_is_the_seeds_and_holds_what_it_drops():
    pool = churn_filters(3_000_000_021, [f"live/d{i}/state" for i in range(40)])
    churn ={"clients": 8, "initial": 20, "rate": 8}
    one = loadgen.churn_schedule(churn, pool, 3_000_000_021, 2, 5.0)
    assert one == loadgen.churn_schedule(churn, pool, 3_000_000_021, 2, 5.0)
    assert one != loadgen.churn_schedule(churn, pool, 3_000_000_022, 2, 5.0)
    held: dict = {}
    for at, k, kind, filt in one:
        if kind == "sub":
            assert filt not in held.setdefault(k, set())
            held[k].add(filt)
        else:
            held[k].remove(filt)
    assert sum(1 for e in one if e[0] == 0) == 20
    # every seed the same number of changes: 20 + 40 SUBSCRIBEs, 40 swaps
    assert sum(1 for e in one if e[2] == "sub") == 60
    assert sum(1 for e in one if e[2] == "unsub") == 40


def test_churn_filters_follow_the_hit_topics():
    hits = ["live/d1/state", "a0/b3/c9/d4", "x/y"]
    pool = churn_filters(5, hits, pool=1000)
    assert set(pool) == {
        "live/d1/state", "+/d1/state", "live/+/state", "live/d1/+",
        "live/d1/#", "a0/b3/c9/d4", "+/b3/c9/d4", "a0/+/c9/d4",
        "a0/b3/+/d4", "a0/b3/c9/+", "a0/b3/#", "a0/b3/c9/#", "x/y", "+/y",
        "x/+"}
    assert all(any(matches(f, t) for t in hits) for f in pool)
    assert churn_filters(5, hits, pool=4) == churn_filters(5, hits, pool=4)


# -- the rule -------------------------------------------------------------------

T0, T1 = 1_000, 10_000


def sent(seq, qos, sent_ns=1_000, plain=None):
    return (0, seq, "t/x", qos, sent_ns, sent_ns, plain or {}, {})


def got(seq, arrival, qos):
    return (b"0:%d:1000" % seq, arrival, qos)


def join(recs, got_by, log, drain_end=9_000):
    d = {"sent": recs, "got": got_by, "unacked": {}}
    return check.join([d], {}, T0, T1,
                      {"log": log, "drain_end_ns": drain_end})


# client c1 (QoS 1) subscribes to t/+ at 100-200 and leaves at 5,000-5,100;
# a message sent at 1,000 reaches the live subscriber s1 first at 1,100
HELD = [("c1", "t/+", 1, "sub", 100, 200), ("c1", "t/+", 1, "unsub",
                                             5_000, 5_100)]


def test_must():
    j = join([sent(0, 1, plain={"s1": 1})],
             {"s1": [got(0, 1_100, 1)], "c1": [got(0, 1_200, 1)]}, HELD)
    assert j["failures"] == [] and j["churn"] == {"must": 1, "may": 0}
    assert (j["attempted"], j["failed"], j["deliveries"]) == (3, 0, 2)
    # a QoS 0 copy it must get and did not: failed, and still correct
    j = join([sent(0, 0, plain={"s1": 0})], {"s1": [got(0, 1_100, 0)]},
             HELD)
    assert (j["attempted"], j["failed"], j["failures"]) == (2, 1, [])
    # at the wrong QoS: a wrong set
    j = join([sent(0, 1, plain={"s1": 1})],
             {"s1": [got(0, 1_100, 1)], "c1": [got(0, 1_200, 0)]}, HELD)
    assert "wrong set" in j["failures"][0]


def test_must_qos1_lost():
    j = join([sent(0, 1, plain={"s1": 1})], {"s1": [got(0, 1_100, 1)]},
             HELD)
    assert (j["attempted"], j["failed"]) == (3, 1)
    assert any("1 QoS 1 deliveries never arrived" in f
               for f in j["failures"])


@pytest.mark.parametrize("log", [
    [("c1", "t/+", 1, "sub", 900, 1_050)],          # SUBACK after p0
    [("c1", "t/+", 1, "sub", 100, 200),             # UNSUBSCRIBE before a1,
     ("c1", "t/+", 1, "unsub", 1_050, 1_300)],      # UNSUBACK after p0
])
def test_may(log):
    for have in ([], [got(0, 1_200, 1)]):
        j = join([sent(0, 1, plain={"s1": 1})],
                 {"s1": [got(0, 1_100, 1)], "c1": have}, log)
        assert j["failures"] == []
        assert (j["attempted"], j["failed"]) == (2, 0)     # s1 + PUBACK
        assert j["churn"] == {"must": 0, "may": 1}
        assert j["deliveries"] == 1 + len(have)
    # a second copy is a wrong set, and a duplicate
    j = join([sent(0, 1, plain={"s1": 1})],
             {"s1": [got(0, 1_100, 1)],
              "c1": [got(0, 1_200, 1), got(0, 1_250, 1)]}, log)
    assert any("wrong set" in f for f in j["failures"])
    assert any("duplicate" in f for f in j["failures"])


@pytest.mark.parametrize("log", [
    [("c1", "t/+", 1, "sub", 1_150, 1_300)],        # SUBSCRIBE after a1
    [("c1", "t/+", 1, "sub", 100, 200),             # UNSUBACK before p0:
     ("c1", "t/+", 1, "unsub", 500, 600)],          # a delivery after it
    [("c1", "u/+", 1, "sub", 100, 200)],            # a filter that misses
])
def test_must_not(log):
    j = join([sent(0, 1, plain={"s1": 1})],
             {"s1": [got(0, 1_100, 1)], "c1": [got(0, 1_200, 1)]}, log)
    assert "wrong set" in j["failures"][0]
    assert j["churn"]["must"] == 0 and j["failed"] == 0


def test_nobody_reached_bounds_by_the_drain():
    # no receiver but the churn client: a1 is the drain's end
    log = [("c2", "t/#", 0, "sub", 100, 200),
           ("c2", "t/#", 0, "unsub", 8_000, 8_100)]
    j = join([sent(0, 1)], {"c2": []}, log, drain_end=7_000)
    assert j["churn"] == {"must": 1, "may": 0} and j["failed"] == 1
    j = join([sent(0, 1)], {"c2": []}, log, drain_end=9_000)
    assert j["churn"] == {"must": 0, "may": 1} and j["failed"] == 0


def test_changes_never_acknowledged_or_of_nothing():
    log = [("c1", "t/+", 1, "sub", 100, None),
           ("c1", "u/+", 1, "unsub", 100, 200)]
    fails = join([sent(0, 0)], {}, log)["failures"]
    assert any("never acknowledged" in f for f in fails)
    assert any("not held" in f for f in fails)


def test_a_parent_shaped_dump_takes_the_parents_path():
    d = {"sent": [(0, 5, "t/x", 1, 1_100, 1_101, {"s1": 1}, {}),
                  (0, 6, "t/x", 0, 1_200, 1_201, {"s1": 0}, {})],
         "got": {"s1": [got(5, 1_300, 1)]}, "unacked": {}}
    plain = check.join([d], {}, T0, T1)
    assert "churn" not in plain
    assert plain == check.join([d], {}, T0, T1, None)
    assert (plain["attempted"], plain["failed"]) == (3, 1)
    # and the churn path with no change in it reads the same but its key
    empty = check.join([d], {}, T0, T1, {"log": [], "drain_end_ns": 9_000})
    assert empty.pop("churn") == {"must": 0, "may": 0}
    assert empty == plain


# -- the rehearsal ----------------------------------------------------------------


def churn_cell() -> dict:
    """fleet-1m at the rehearsal's table size under the traffic file of
    ``testdata/churn-flood.json``."""
    with open(os.path.join(BENCH, "configs", "fleet-1m.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "rehearse", "fleet-1m.json")) as fh:
        config.update(json.load(fh))
    traffic_path = os.path.join(BENCH, "testdata", "churn-flood.json")
    with open(traffic_path) as fh:
        traffic = json.load(fh)
    return {"name": "rehearsal.churn", "chips": 1, "config": config,
            "config_path": None, "traffic": traffic,
            "traffic_path": traffic_path, "end_to_end": [], "per_layer": []}


def harness():
    """``run``, once the native libraries are built from their sources
    (before the package loads them, as ``run.main`` does)."""
    subprocess.run(["make", "-C", os.path.join(ROOT, "native")], check=True,
                   stdout=subprocess.DEVNULL)
    import run
    return run


def rehearse(workdir: str) -> dict:
    run = harness()
    args = argparse.Namespace(seed=3_000_000_042, seconds=3.0, trace=0,
                              keep_trace=None)
    result = {"failures": [], "attempted": 0, "failed": 0}
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    asyncio.run(run.measure(args, churn_cell(), device, run.CompileWatch(),
                            workdir, result))
    return result


def test_churn_rehearsal_is_correct(tmp_path):
    result = rehearse(str(tmp_path))
    assert result["failures"] == []
    assert result["failed"] == 0 and result["attempted"] > 0
    run = result["run"]
    assert run["joined"]["churn"]["must"] > 0
    gens = run["generators"]
    assert sum(g["subscribes"] for g in gens) == 160 + 24
    assert sum(g["unsubscribes"] for g in gens) == 160 + 24
    assert all(g["suback_max_ms"] >= g["suback_p50_ms"] > 0 for g in gens)
    assert run["table_versions"] > 0
    import readers
    assert readers.run_number(run, "table_versions") == run["table_versions"]
    assert readers.run_number(run, "window_programs") >= 0
    assert readers.run_number(run, "nothing_by_that_name") is None
    assert readers.generator_field(run, "suback_p50_ms") > 0


@pytest.mark.parametrize("fault, says", [
    ("churn_ghost", "delivered to a wrong set"),
    ("churn_deaf", "QoS 1 deliveries never arrived"),
])
def test_churn_faults_make_it_incorrect(tmp_path, monkeypatch, fault, says):
    harness()
    from maxmq_tpu.broker.server import Broker
    for key, handler in list(Broker._DISPATCH.items()):
        monkeypatch.setitem(Broker._DISPATCH, key, handler)
    import faults_churn
    getattr(faults_churn, fault)()
    result = rehearse(str(tmp_path))
    assert any(says in f for f in result["failures"]), result["failures"]
