"""The deployment ``tree-100k`` (perfbench/configs/tree-100k.json;
``BASELINE.json`` ``configs[2]``: a five-level ``+``/``#`` wildcard
tree) at a size the CPU carries: 2,000 stored filters of ``tree_table``
and the live recipe at a tenth (40 watchers, 147 filters that nest).
Four answers are held against each other on 5,000 seeded leaves: the
trie, the engine's host probe, the device path on the CPU backend, and a
plain scan kept here. Then the rules of overlap one by one
[MQTT-3.3.5-1], the same through a served broker over TCP, and what this
deployment added to the program: the ``resolve`` stage of a sampled
publish and the gauge of the widest fold."""

from __future__ import annotations

import asyncio
import contextlib
import functools
import io
import os
import random
import sys
from types import SimpleNamespace

import pytest

from maxmq_tpu.bootstrap import run_server
from maxmq_tpu.matching.sig import SigEngine
from maxmq_tpu.matching.trie import TopicIndex, merge_subscription
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.protocol import Subscription
from maxmq_tpu.utils.config import Config
from maxmq_tpu.utils.logger import Logger

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
sys.path.insert(0, BENCH)

import generators  # noqa: E402  (perfbench's: recipes found by name)

from test_fanout_wide import write_store  # noqa: E402

SEED = 3_000_000_039        # more than 32 signed bits hold
STORED, SCALE, LEAVES = 2000, 0.1, 5000
PATHS = ["trie", "host", "device"]
MATCHERS = ["trie", "sig"]


def matches(filt: list, levels: list) -> bool:
    """MQTT 4.7, written straight down: importing nothing of the
    program, nor of the benchmark's reference."""
    if levels[0].startswith("$") and filt[0] in ("+", "#"):
        return False
    for i, name in enumerate(filt):
        if name == "#":
            return True                 # the parent level itself included
        if i >= len(levels) or name not in ("+", levels[i]):
            return False
    return len(filt) == len(levels)


def scan(held: list, topic: str) -> dict:
    """The plain scan: ``held`` is [(client id, filter levels, qos)];
    client id -> [the QoS of each of its filters that match]."""
    levels = topic.split("/")
    out: dict = {}
    for cid, filt, qos in held:
        if matches(filt, levels):
            out.setdefault(cid, []).append(qos)
    return out


@functools.lru_cache(maxsize=None)
def tree():
    """(stored filters, live plan, everything held as the scan wants it,
    5,000 leaves from the seed)."""
    stored = generators.find("tree_table")(STORED, SEED)
    plan, groups, _hits = generators.find("tree_live")(SEED, scale=SCALE)
    assert groups == {} and len(plan) == 40
    assert sum(len(v) for v in plan.values()) == 147
    held = [(f"cl-{i}", f.split("/"), i % 3) for i, f in enumerate(stored)]
    held += [(cid, f.split("/"), q)
             for cid, subs in plan.items() for f, q in subs]
    draw = generators.find("tree_topics")(SEED, [])
    rng = random.Random(SEED + 5)
    return stored, plan, held, [draw(rng) for _ in range(LEAVES)]


@functools.lru_cache(maxsize=None)
def scanned() -> list:
    """The scan's answer for every leaf, once for the three paths."""
    _stored, _plan, held, leaves = tree()
    return [scan(held, t) for t in leaves]


def build_engine(subscriptions) -> SigEngine:
    """[(client id, filter, qos)] as a broker's restore would index it,
    compiled once and served as ``bootstrap.build_matcher`` serves it."""
    index = TopicIndex()
    for cid, f, q in subscriptions:
        index.subscribe(cid, Subscription(filter=f, qos=q))
    eng = SigEngine(index, auto_refresh=False)
    eng.emit_intents = True
    eng.route_small = False
    return eng


@functools.lru_cache(maxsize=None)
def engine() -> SigEngine:
    _stored, _plan, held, _leaves = tree()
    return build_engine((cid, "/".join(f), q) for cid, f, q in held)


def ask(eng: SigEngine, path: str, topics: list) -> list:
    if path == "trie":
        return [eng.index.subscribers(t) for t in topics]
    fn = (eng.subscribers_host_batch if path == "host"
          else eng.subscribers_fixed_batch)
    return [r for lo in range(0, len(topics), 256)
            for r in fn(topics[lo:lo + 256])]


def named(result) -> dict:
    """client id -> (granted QoS, filters folded) of a match result."""
    subs = (result.to_set() if hasattr(result, "to_set")
            else result).subscriptions
    return {cid: (sub.qos, sub.folded) for cid, sub in subs.items()}


# -- four answers to 5,000 leaves ------------------------------------------


@pytest.mark.parametrize("path", PATHS)
def test_path_names_the_scans_receivers_at_the_scans_qos(path):
    _stored, plan, _held, leaves = tree()
    results = ask(engine(), path, leaves)
    deliveries = folded = differ = 0
    for topic, result, want in zip(leaves, results, scanned()):
        assert not result.shared
        # every client the scan names, stored or live, once, at the
        # highest QoS of its filters that match, folded from that many
        assert named(result) == {cid: (max(qos), len(qos))
                                 for cid, qos in want.items()}, topic
        live = {cid: qos for cid, qos in want.items() if cid in plan}
        deliveries += len(live)
        folded += sum(len(q) > 1 for q in live.values())
        differ += sum(len(set(q)) > 1 for q in live.values())
    # a tenth of the population gives a tenth of 7.6 a message; a fifth
    # of them reach a session through several of its filters, some of
    # those at different QoS
    assert 0.6 < deliveries / LEAVES < 0.95
    assert 0.15 < folded / deliveries < 0.32
    assert differ > 20


# -- the rules of overlap, one by one ---------------------------------------

RULES = {
    # two filters of one session, QoS 0 and 1: one copy at QoS 1
    "two_filters_qos_0_and_1": (
        [("w", "a1/#", 0), ("w", "a1/b2/#", 1), ("v", "a1/b2/#", 0)],
        "a1/b2/c3/d4/e5", {"w": (1, 2), "v": (0, 1)}),
    # the same, the wide filter holding the higher QoS
    "the_wide_filter_grants_more": (
        [("w", "a1/#", 1), ("w", "a1/b2/c3/+/+", 0), ("w", "a1/b2/#", 0)],
        "a1/b2/c3/d4/e5", {"w": (1, 3)}),
    # a '#' parent and a '+' sibling of one session, and of two
    "hash_parent_and_plus_sibling": (
        [("w", "a1/b2/#", 0), ("w", "a1/+/c3/d4/e5", 1),
         ("v", "a1/+/c3/d4/e5", 0), ("u", "a1/b2/c3/d4/+", 1),
         ("u", "a1/b9/#", 1)],
        "a1/b2/c3/d4/e5", {"w": (1, 2), "v": (0, 1), "u": (1, 1)}),
    # '#' also matches its parent level; '+' does not match two levels
    "hash_matches_the_parent_itself": (
        [("w", "a1/b2/#", 1), ("w", "a1/+", 0), ("v", "a1/+/+", 1)],
        "a1/b2", {"w": (1, 2)}),
    # stored filters match, no live one: somebody is named, nobody live
    "only_stored_filters_match": (
        [("cl-0", "a7/#", 2), ("cl-1", "a7/+/c3/d4/e5", 0),
         ("w", "a1/#", 1)],
        "a7/b2/c3/d4/e5", {"cl-0": (2, 1), "cl-1": (0, 1)}),
}


@functools.lru_cache(maxsize=None)
def rule_engine(rule: str) -> SigEngine:
    return build_engine(RULES[rule][0])


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("rule", RULES)
def test_overlap_rule(rule, path):
    subscriptions, topic, want = RULES[rule]
    (result,) = ask(rule_engine(rule), path, [topic])
    assert named(result) == want
    # the scan kept here says the same
    held = [(cid, f.split("/"), q) for cid, f, q in subscriptions]
    assert {cid: (max(q), len(q))
            for cid, q in scan(held, topic).items()} == want
    # resolved against a registry that holds the live sessions alone: a
    # stored client is matched and resolves to nobody
    registry = {cid: SimpleNamespace(id=cid) for cid in want
                if not cid.startswith("cl-")}
    pairs, shared, matched, resolved = result.resolve(registry)
    assert shared == {} and matched == len(want)
    assert resolved == len(pairs) == len(registry)
    assert {c.id: (sub.qos, sub.folded) for c, sub in pairs} == \
        {cid: want[cid] for cid in registry}


def test_folded_counts_filters_and_is_no_part_of_what_is_compared():
    low = Subscription(filter="a0/#", qos=0)
    high = Subscription(filter="a0/b1/#", qos=1)
    assert merge_subscription(None, low, low.filter) is low
    assert low.folded == 1
    both = merge_subscription(low, high, high.filter)
    assert (both.qos, both.folded) == (1, 2)
    third = merge_subscription(both, Subscription(filter="+/b1/#", qos=0),
                               "+/b1/#")
    assert (third.qos, third.folded) == (1, 3) and both.folded == 2
    assert both == Subscription(filter="a0/b1/#", qos=1)
    assert "folded" not in repr(both)
    # a v5 identifier alone makes a copy, not a fold
    tagged = Subscription(filter="a0/#", qos=0, identifier=7)
    alone = merge_subscription(None, tagged, tagged.filter)
    assert alone is not tagged and alone.folded == 1
    assert alone.identifiers == {"a0/#": 7}


# -- through a served broker ------------------------------------------------


class Served:
    def __init__(self, broker, plan, held) -> None:
        self.broker, self.plan, self.held = broker, plan, held
        self.port = broker.listeners.get("tcp")._server.sockets[0] \
            .getsockname()[1]
        self.subs: dict[str, MQTTClient] = {}
        self.pub = MQTTClient(client_id="load-p0")

    async def connect(self) -> None:
        for cid in self.plan:
            c = self.subs[cid] = MQTTClient(client_id=cid, clean_start=False)
            await c.connect("127.0.0.1", self.port)
            assert c.session_present is True
        await self.pub.connect("127.0.0.1", self.port)

    def receivers(self, topic: str) -> dict:
        """live client id -> the QoS of its filters that match."""
        return {cid: q for cid, q in scan(self.held, topic).items()
                if cid in self.plan}

    async def deliveries(self, want: int) -> dict:
        """client id -> [(topic, payload, qos)], once ``want`` came."""
        for _ in range(1200):
            if sum(c.messages.qsize() for c in self.subs.values()) >= want:
                break
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.2)        # a copy too many would come now
        got: dict = {}
        for cid, c in self.subs.items():
            while not c.messages.empty():
                m = c.messages.get_nowait()
                got.setdefault(cid, []).append((m.topic, m.payload, m.qos))
        return got


@contextlib.asynccontextmanager
async def served(tmp_path, matcher: str, **conf):
    stored, plan, held, _leaves = tree()
    path = str(tmp_path / "store.db")
    n_subs = write_store(path, stored, plan)
    config = Config(mqtt_tcp_address="127.0.0.1:0", metrics_enabled=False,
                    matcher=matcher, mqtt_sys_topic_interval=0,
                    log_level="warn", storage_backend="sqlite",
                    storage_path=path, **conf)
    ready, stop, built = asyncio.Event(), asyncio.Event(), []
    server = asyncio.ensure_future(run_server(
        config, Logger(out=io.StringIO(), fmt="json"), ready=ready,
        stop=stop, broker_out=built))
    s = None
    try:
        await asyncio.wait_for(ready.wait(), timeout=120)
        broker = built[0]
        assert broker.topics.subscription_count == n_subs
        s = Served(broker, plan, held)
        await s.connect()
        yield s
        if matcher == "sig":    # no answer came from a path that failed
            assert (broker.matcher.error_fallbacks,
                    broker.matcher_degrades) == (0, 0)
    finally:
        if s is not None:
            for c in list(s.subs.values()) + [s.pub]:
                await c.close()
        stop.set()
        await asyncio.wait_for(server, timeout=120)


def overlapped_leaves(s: Served, n: int) -> list:
    """The first ``n`` leaves on which some session's filters meet at
    different QoS, then a run of ordinary ones."""
    leaves = tree()[3]
    mixed = [t for t in leaves
             if any(len(set(q)) > 1 for q in s.receivers(t).values())]
    assert len(mixed) >= n
    return mixed[:n] + leaves[:100]


@pytest.mark.parametrize("matcher", MATCHERS)
async def test_one_copy_a_session_at_the_highest_qos_over_tcp(
        tmp_path, matcher):
    """Leaves on which a session's filters meet at different QoS, at
    both publish QoS, and a run of ordinary ones: each live session the
    scan names gets the message once, at the highest QoS of its filters
    that match, capped by the publish's. With sampling off the stage
    allocates nothing, and the gauge holds the widest fold."""
    async with served(tmp_path, matcher) as s:
        over, tracer = s.broker.overload, s.broker.tracer
        assert tracer.sample_n == 0 and over.fanout_overlap_widest == 0
        want: dict = {}
        widest = 0
        for k, topic in enumerate(overlapped_leaves(s, 8)):
            qos = k % 2
            payload = b"0:%d" % k
            await s.pub.publish(topic, payload, qos=qos, timeout=60)
            for cid, granted in s.receivers(topic).items():
                want.setdefault(cid, []).append(
                    (topic, payload, min(qos, max(granted))))
                widest = max(widest, len(granted))
        n = sum(len(v) for v in want.values())
        assert n > 20 and widest >= 2
        got = await s.deliveries(n)
        assert got == want              # once each, in order, at that QoS
        assert over.fanout_overlap_widest == widest
        assert over.fanout_resolved == n
        assert over.fanout_matched > n      # the sessionless cl-<i>
        assert tracer.allocations == 0 and tracer.sampled == 0
        assert tracer.stage_hist["resolve"].count == 0
        assert tracer.report()["entries"] == []


@pytest.mark.parametrize("matcher", MATCHERS)
async def test_resolve_stage_lies_inside_the_fanout_of_a_sampled_publish(
        tmp_path, matcher):
    async with served(tmp_path, matcher, trace_sample_n=1,
                      trace_slow_ms=0.0, trace_ring=256) as s:
        tracer = s.broker.tracer
        topic = overlapped_leaves(s, 1)[0]
        await s.pub.publish(topic, b"0:0", qos=1)
        await s.pub.publish("nobody/listens", b"0:1", qos=1)
        want = s.receivers(topic)
        got = await s.deliveries(len(want))
        assert got == {cid: [(topic, b"0:0", max(q))]
                       for cid, q in want.items()}
        by_topic: dict = {}
        for _ in range(200):
            by_topic = {e["topic"]: e for e in tracer.report()["entries"]}
            if len(by_topic) >= 2:
                break
            await asyncio.sleep(0.05)
        for t in (topic, "nobody/listens"):     # every fan-out resolves
            spans = {sp["stage"]: sp for sp in by_topic[t]["spans"]}
            fan, res = spans["fanout"], spans["resolve"]
            assert res["parent"] == "fanout"
            assert fan["off_us"] <= res["off_us"]
            assert res["off_us"] + res["dur_us"] <= \
                fan["off_us"] + fan["dur_us"] + 1
            # a part of the fan-out, which is counted whole
            assert by_topic[t]["critical_sum_ms"] <= by_topic[t]["e2e_ms"] \
                + 0.001
        assert tracer.stage_hist["resolve"].count == 2
        assert s.broker.overload.fanout_overlap_widest >= 2


def test_resolve_is_a_part_of_fanout_outside_the_critical_path():
    from maxmq_tpu.trace import CRITICAL_STAGES, FANOUT_PARTS, STAGES
    assert FANOUT_PARTS == ("resolve", "share_pick")
    for stage in FANOUT_PARTS:
        assert stage in STAGES and stage not in CRITICAL_STAGES
    assert "fanout" in CRITICAL_STAGES


def test_the_gauge_is_exported():
    from maxmq_tpu.broker import Broker, BrokerOptions
    from maxmq_tpu.metrics import Registry, register_broker_metrics
    broker = Broker(BrokerOptions())
    reg = Registry()
    register_broker_metrics(reg, broker)
    assert "maxmq_broker_fanout_overlap_widest 0" in reg.expose()
    broker.overload.fanout_overlap_widest = 3
    text = reg.expose()
    assert "# TYPE maxmq_broker_fanout_overlap_widest gauge" in text
    assert "maxmq_broker_fanout_overlap_widest 3" in text


def test_the_capture_gives_resolve_a_row_cut_out_of_deliver():
    """``tools/trace_gaps.py`` carves ``maxmq.resolve`` out of the
    ``maxmq.deliver`` around it, as it does the picks."""
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "tools"))
    try:
        import trace_gaps
    finally:
        sys.path.pop(0)
    assert "maxmq.resolve" in trace_gaps.CARVED
    rows = trace_gaps.carve([("maxmq.deliver", 0, 100),
                             ("maxmq.resolve", 10, 30),
                             ("maxmq.share", 40, 45)])
    time = {}
    for name, lo, hi in rows:
        time[name] = time.get(name, 0) + hi - lo
    assert time == {"maxmq.deliver": 75, "maxmq.resolve": 20,
                    "maxmq.share": 5}


for _case in (test_one_copy_a_session_at_the_highest_qos_over_tcp,
              test_resolve_stage_lies_inside_the_fanout_of_a_sampled_publish):
    _case._async_timeout = 600
