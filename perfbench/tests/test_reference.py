"""The plain reference against cases written down from the MQTT
specifications (3.1.1 section 4.7, 5.0 sections 4.7 and 4.8.2)."""

import pytest

from reference import Reference, matches, split_share

CASES = [
    # (filter, topic, matches) -- 4.7.1.2, the multi-level wildcard
    ("sport/tennis/player1/#", "sport/tennis/player1", True),
    ("sport/tennis/player1/#", "sport/tennis/player1/ranking", True),
    ("sport/tennis/player1/#", "sport/tennis/player1/score/wimbledon", True),
    ("sport/#", "sport", True),
    ("#", "sport/tennis", True),
    ("sport/tennis/#", "sport/golf", False),
    # 4.7.1.3, the single-level wildcard
    ("sport/tennis/+", "sport/tennis/player1", True),
    ("sport/tennis/+", "sport/tennis/player1/ranking", False),
    ("sport/+", "sport", False),
    ("sport/+", "sport/", True),
    ("+/+", "/finance", True),
    ("/+", "/finance", True),
    ("+", "/finance", False),
    ("+/tennis/#", "sport/tennis", True),
    ("sport/+/player1", "sport/tennis/player1", True),
    # exact
    ("a/b/c", "a/b/c", True),
    ("a/b/c", "a/b", False),
    ("a/b", "a/b/c", False),
    ("a/b/c", "a/b/d", False),
    ("A/b", "a/b", False),
    # 4.7.2, topics beginning with $
    ("#", "$SYS/broker/uptime", False),
    ("+/monitor/Clients", "$SYS/monitor/Clients", False),
    ("$SYS/#", "$SYS/broker/uptime", True),
    ("$SYS/monitor/+", "$SYS/monitor/Clients", True),
    ("$SYS/#", "$SYS", True),
]


@pytest.mark.parametrize("filt,topic,want", CASES)
def test_matches(filt, topic, want):
    assert matches(filt, topic) is want


def test_split_share():
    assert split_share("$share/g1/a/+/b") == ("g1", "a/+/b")
    assert split_share("$share/g/#") == ("g", "#")
    assert split_share("a/$share/b") == ("", "a/$share/b")


def test_receivers_merge_and_groups():
    ref = Reference({
        "c1": [("a/#", 0), ("a/+", 1)],          # overlap: highest QoS
        "c2": [("$share/g/a/+", 1)],
        "c3": [("$share/g/a/+", 0), ("b/x", 1)],
        "c4": [("$share/h/#", 1)],
    })
    plain, shared = ref.receivers("a/x")
    assert plain == {"c1": 1}
    assert shared == {"g": {"c2": 1, "c3": 0}, "h": {"c4": 1}}
    plain, shared = ref.receivers("b/x")
    assert plain == {"c3": 1} and shared == {"h": {"c4": 1}}
    # a shared subscription's filter follows 4.7.2 too
    assert ref.receivers("$SYS/x") == ({}, {})
