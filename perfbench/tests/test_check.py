"""The join that decides ``correct``, on hand-made dumps."""

import check

T0, T1 = 1_000, 2_000
GROUPS = {"g": ["s2", "s3"]}


def sent(pub, seq, qos, plain, shared, due=1_100):
    return (pub, seq, "t", qos, due, due + 1, plain, shared)


def got(pub, seq, arrival, qos, due=1_100):
    return (b"%d:%d:%d" % (pub, seq, due), arrival, qos)


def dump(sent_recs, got_by_sub, unacked=None):
    return {"sent": sent_recs, "got": got_by_sub, "unacked": unacked or {}}


def test_right_run():
    d = dump([sent(0, 5, 1, {"s1": 1}, {"g": {"s2": 0, "s3": 1}}),
              sent(0, 6, 0, {"s1": 1}, {})],
             {"s1": [got(0, 4, 1_050, 1),            # a warm-up straggler
                     got(0, 5, 1_200, 1), got(0, 6, 2_100, 0)],
              "s3": [got(0, 5, 1_300, 1)]})
    j = check.join([d], GROUPS, T0, T1)
    assert j["failures"] == []
    # two plain deliveries, one group, one PUBACK
    assert (j["attempted"], j["failed"]) == (4, 0)
    assert j["deliveries"] == 3 and j["in_window"] == 2
    assert sorted(j["latencies_ns"]) == [100, 200, 1_000]


def test_group_served_twice_and_stranger():
    d = dump([sent(0, 0, 0, {}, {"g": {"s2": 0, "s3": 0}})],
             {"s2": [got(0, 0, 1_200, 0)], "s3": [got(0, 0, 1_200, 0)],
              "s1": []})
    assert "wrong set" in check.join([d], GROUPS, T0, T1)["failures"][0]
    d = dump([sent(0, 0, 0, {}, {})], {"s1": [got(0, 0, 1_200, 0)]})
    assert "wrong set" in check.join([d], GROUPS, T0, T1)["failures"][0]


def test_wrong_qos_duplicate_and_order():
    d = dump([sent(0, 0, 1, {"s1": 1}, {})], {"s1": [got(0, 0, 1_200, 0)]})
    assert "wrong set" in check.join([d], GROUPS, T0, T1)["failures"][0]
    d = dump([sent(0, 0, 0, {"s1": 0}, {}), sent(0, 1, 0, {"s1": 0}, {})],
             {"s1": [got(0, 1, 1_200, 0), got(0, 0, 1_300, 0)]})
    assert any("order" in f for f in
               check.join([d], GROUPS, T0, T1)["failures"])
    d = dump([sent(0, 0, 0, {"s1": 0}, {})],
             {"s1": [got(0, 0, 1_200, 0), got(0, 0, 1_300, 0)]})
    fails = check.join([d], GROUPS, T0, T1)["failures"]
    assert any("order" in f for f in fails)
    assert any("wrong set" in f for f in fails)


def test_shed_qos0_fails_without_being_wrong_and_lost_qos1_is_wrong():
    d = dump([sent(0, 0, 0, {"s1": 1}, {})], {"s1": []})
    j = check.join([d], GROUPS, T0, T1)
    assert (j["attempted"], j["failed"], j["failures"]) == (1, 1, [])
    d = dump([sent(0, 0, 1, {"s1": 1}, {})], {"s1": []}, {0: [0]})
    j = check.join([d], GROUPS, T0, T1)
    assert (j["attempted"], j["failed"]) == (2, 2)
    assert any("never arrived" in f for f in j["failures"])
    assert any("PUBACKed" in f for f in j["failures"])


def test_delivery_nobody_sent():
    d = dump([sent(0, 3, 0, {}, {})], {"s1": [got(0, 9, 1_200, 0)]})
    assert any("nobody sent" in f for f in
               check.join([d], GROUPS, T0, T1)["failures"])
