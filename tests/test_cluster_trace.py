"""Cluster observability plane e2e suite (ADR 017): cross-node trace
propagation over a real 3-node TCP line (one correlated trace,
bridge_in child spans, origin-attached remote reports, per-node
Perfetto tracks), old-peer envelope compatibility (the flag bit is
capability-negotiated away), clock-skew estimation with scripted
per-broker clocks, the federated ``/cluster/metrics`` page +
cardinality bounds, the ADR-015 closure items (QoS2 release-leg span,
per-bucket journal attribution), and the zero-allocations-when-off
contract across the propagation path."""

import asyncio
import importlib.util
import json
import os
import time
import urllib.request

import pytest

from maxmq_tpu import faults
from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities, TCPListener
from maxmq_tpu.cluster import ClusterManager, PeerSpec
from maxmq_tpu.hooks import AllowHook
from maxmq_tpu.hooks.journal import WriteBehindStore
from maxmq_tpu.hooks.storage import MemoryStore, StorageHook
from maxmq_tpu.metrics import MetricsServer, Registry, register_broker_metrics
from maxmq_tpu.mqtt_client import MQTTClient


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()
    faults.REGISTRY.reset_clock()


def _load_script(name: str):
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", name)
    spec = importlib.util.spec_from_file_location(
        name.replace(".py", "_mod"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


async def wait_for(predicate, timeout: float = 10.0, what: str = ""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(0.02)
    raise AssertionError(f"condition not reached in {timeout}s: {what}")


async def make_node(hooks=(), **caps) -> Broker:
    caps.setdefault("sys_topic_interval", 0)
    b = Broker(BrokerOptions(capabilities=Capabilities(**caps)))
    b.add_hook(AllowHook())
    for h in hooks:
        b.add_hook(h)
    listener = b.add_listener(TCPListener("t", "127.0.0.1:0"))
    await b.serve()
    b.test_port = listener._server.sockets[0].getsockname()[1]
    return b


async def make_cluster(topology: dict[str, list[str]], **kw):
    kw.setdefault("keepalive", 0.5)
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.5)
    brokers: dict[str, Broker] = {}
    managers: dict[str, ClusterManager] = {}
    for name in topology:
        brokers[name] = await make_node()
    for name, peers in topology.items():
        mgr = ClusterManager(
            brokers[name], name,
            [PeerSpec(p, "127.0.0.1", brokers[p].test_port)
             for p in peers], **kw)
        brokers[name].attach_cluster(mgr)
        await mgr.start()
        managers[name] = mgr
    return brokers, managers


async def close_cluster(brokers: dict[str, Broker]) -> None:
    for b in brokers.values():
        await b.close()


async def wait_caps(managers, timeout: float = 10.0) -> None:
    """Capability hellos exchanged on every connected link."""
    def all_caps():
        for mgr in managers.values():
            for peer in mgr.links:
                st = mgr.membership.get(peer)
                if st is None or "fwd-trace" not in st.caps:
                    return False
        return True
    await wait_for(all_caps, timeout, "capability negotiation")


async def connect(broker: Broker, client_id: str, **kw) -> MQTTClient:
    c = MQTTClient(client_id=client_id, **kw)
    await c.connect("127.0.0.1", broker.test_port)
    return c


LINE = {"A": ["B"], "B": ["A", "C"], "C": ["B"]}
PAIR = {"A": ["B"], "B": ["A"]}


# ----------------------------------------------------------------------
# Cross-node trace propagation
# ----------------------------------------------------------------------


async def test_three_node_line_single_correlated_trace():
    """A sampled publish at A delivered at B and C (2 hops) produces
    ONE correlated trace: the origin's entry gains remote reports from
    both receiving nodes with bridge_in spans and hop counts, the
    Chrome export grows per-node tracks, and the v5 subscriber's
    delivery carries the <origin>:<id> grep key."""
    brokers, mgrs = await make_cluster(LINE)
    try:
        sub_b = await connect(brokers["B"], "sub-b", version=5)
        sub_c = await connect(brokers["C"], "sub-c", version=5)
        await sub_b.subscribe("t/#")
        await sub_c.subscribe("t/#")
        await wait_for(lambda: mgrs["A"].routes.nodes_for("t/x"),
                       what="2-hop routes at A")
        await wait_caps(mgrs)
        brokers["A"].tracer.sample_n = 1
        pub = await connect(brokers["A"], "pub")
        await pub.publish("t/x", b"payload")
        mb = await sub_b.next_message(timeout=5)
        mc = await sub_c.next_message(timeout=5)
        assert brokers["B"].tracer.adopted == 1
        assert brokers["C"].tracer.adopted == 1

        # the origin's entry collects both nodes' span reports
        await wait_for(
            lambda: brokers["A"].tracer.remote_attached >= 2,
            what="remote span reports attached at origin")
        entry = next(e for e in brokers["A"].tracer.report()["entries"]
                     if e["topic"] == "t/x")
        remote = {r["node"]: r for r in entry["remote"]}
        assert set(remote) == {"B", "C"}
        assert remote["B"]["hops"] == 1 and remote["C"]["hops"] == 2
        for r in remote.values():
            assert "bridge_in" in {s["stage"] for s in r["spans"]}
            assert r["e2e_ms"] >= 0
        # ONE correlation id across the line: the receiving nodes'
        # adopted entries carry the origin's id + node tag
        for node in ("B", "C"):
            adopted = brokers[node].tracer.report()["entries"][0]
            assert adopted["id"] == entry["id"]
            assert adopted["origin"] == "A"
            assert {"bridge_in", "fanout"} <= \
                {s["stage"] for s in adopted["spans"]}
        # per-hop cross-node e2e histograms on the origin
        cross = brokers["A"].tracer.cross_quantiles()
        assert "hops1" in cross and "hops2" in cross

        # Chrome export: per-node named tracks, JSON-serializable
        doc = json.loads(json.dumps(brokers["A"].tracer.chrome_events()))
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert {"node A", "node B", "node C"} <= names
        # the v5 deliveries carried the cross-node grep key
        want = f"A:{entry['id']}"
        assert mb.trace == want and mc.trace == want
        for c in (pub, sub_b, sub_c):
            await c.disconnect()
    finally:
        await close_cluster(brokers)


async def test_old_peer_gets_pre017_envelope():
    """Version negotiation: a peer that never announced ``fwd-trace``
    (an old binary) receives the plain envelope — the flag bit and
    trace segment never cross the wire to it."""
    brokers, mgrs = await make_cluster(PAIR)
    try:
        sub = await connect(brokers["B"], "sub")
        await sub.subscribe("t/#")
        await wait_for(lambda: mgrs["A"].routes.nodes_for("t/x"),
                       what="routes at A")
        await wait_caps(mgrs)
        link = mgrs["A"].links["B"]
        sent = []
        orig = link.forward
        link.forward = lambda topic, payload, qos=0, **kw: (
            sent.append(topic), orig(topic, payload, qos=qos, **kw))[1]
        brokers["A"].tracer.sample_n = 1
        pub = await connect(brokers["A"], "pub")

        # capable peer: flag bit + trace segment present
        await pub.publish("t/x", b"new")
        assert (await sub.next_message(timeout=5)).payload == b"new"
        flags_new = sent[-1].split("/")[6]
        assert "t" in flags_new
        # simulate an old peer: no announced caps -> plain envelope
        mgrs["A"].membership.peers["B"].caps = frozenset()
        await pub.publish("t/x", b"old")
        assert (await sub.next_message(timeout=5)).payload == b"old"
        flags_old = sent[-1].split("/")[6]
        assert "t" not in flags_old
        assert len(sent[-1].split("/")) == len(sent[-2].split("/")) - 1
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await close_cluster(brokers)


async def test_fwd_envelope_flag_parsing_compat():
    """Inbound compatibility: pre-017 envelopes parse unchanged, a
    traced envelope adopts, unknown future flag characters are
    tolerated, and a malformed trace segment is rejected — never
    misread as topic levels."""
    from maxmq_tpu.protocol.codec import FixedHeader, PacketType as PT
    from maxmq_tpu.protocol.packets import Packet

    brokers, mgrs = await make_cluster(PAIR)
    try:
        a = mgrs["A"]

        async def fwd(topic: str) -> bool:
            p = Packet(fixed=FixedHeader(type=PT.PUBLISH),
                       topic=topic, payload=b"x")
            before = a.forwards_delivered
            await a._handle_fwd(None, "B", topic.split("/"), p)
            return a.forwards_delivered > before

        assert await fwd("$cluster/fwd/B/1/1/1/0/t/x")      # pre-017
        assert brokers["A"].tracer.adopted == 0
        assert await fwd("$cluster/fwd/B/1/2/1/0t/7.1000/t/x")
        assert brokers["A"].tracer.adopted == 1
        adopted = brokers["A"].tracer.report()["entries"][-1]
        assert adopted["id"] == 7 and adopted["origin"] == "B"
        # future flag characters are ignored, not fatal
        assert await fwd("$cluster/fwd/B/1/3/1/0z/t/x")
        # malformed trace segment: rejected outright
        rejected = a.inbound_rejected
        assert not await fwd("$cluster/fwd/B/1/4/1/0t/garbage/t/x")
        assert a.inbound_rejected == rejected + 1
    finally:
        await close_cluster(brokers)


async def test_zero_allocations_when_off_across_the_wire():
    """Sampling off at the origin: no trace context crosses the wire
    and NO node allocates a trace — the ADR-015 zero-alloc contract
    extended cluster-wide."""
    brokers, mgrs = await make_cluster(PAIR)
    try:
        sub = await connect(brokers["B"], "sub")
        await sub.subscribe("t/#")
        await wait_for(lambda: mgrs["A"].routes.nodes_for("t/x"),
                       what="routes at A")
        await wait_caps(mgrs)
        pub = await connect(brokers["A"], "pub")
        for i in range(10):
            await pub.publish("t/x", b"m")
        for i in range(10):
            await sub.next_message(timeout=5)
        for node in ("A", "B"):
            t = brokers[node].tracer
            assert t.allocations == 0
            assert t.adopted == 0 and t.adopted_open == 0
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await close_cluster(brokers)


# ----------------------------------------------------------------------
# Clock skew
# ----------------------------------------------------------------------


async def test_clock_skew_estimated_and_applied():
    """Per-broker scripted clock offsets (through the fault-registry
    clock the tracers read) are recovered by the probe within the
    loopback RTT, exposed on the metrics page, and applied when
    translating a forwarded trace's t0."""
    brokers, mgrs = await make_cluster(PAIR)
    try:
        await wait_for(lambda: mgrs["A"].links["B"].connected
                       and mgrs["B"].links["A"].connected,
                       what="links up")
        # B's clock runs 50ms ahead of A's (scripted via the shared
        # faults.REGISTRY.clock_ns base + a per-broker tracer offset)
        off_ns = 50_000_000
        brokers["B"].tracer._clock = \
            lambda: faults.REGISTRY.clock_ns() + off_ns
        for name in ("A", "B"):
            for st in mgrs[name].membership.peers.values():
                st.skew_ns = st.rtt_ns = 0.0
                st.skew_samples = 0     # discard the link-up estimate
        mgrs["A"].telemetry.probe_peer(mgrs["A"].links["B"])
        mgrs["B"].telemetry.probe_peer(mgrs["B"].links["A"])
        await wait_for(
            lambda: mgrs["A"].membership.peers["B"].skew_samples >= 1
            and mgrs["B"].membership.peers["A"].skew_samples >= 1,
            what="skew estimates")
        skew_ab = mgrs["A"].membership.peers["B"].skew_ns
        skew_ba = mgrs["B"].membership.peers["A"].skew_ns
        assert abs(skew_ab - off_ns) < 25_000_000, skew_ab
        assert abs(skew_ba + off_ns) < 25_000_000, skew_ba

        reg = Registry()
        register_broker_metrics(reg, brokers["A"])
        assert 'maxmq_cluster_peer_clock_skew_ms{peer="B"}' \
            in reg.expose()

        # applied on adoption: B's trace of a forward from A reads a
        # sane (sub-second) e2e despite the 50ms clock offset
        sub = await connect(brokers["B"], "sub")
        await sub.subscribe("t/#")
        await wait_for(lambda: mgrs["A"].routes.nodes_for("t/x"),
                       what="routes at A")
        await wait_caps(mgrs)
        brokers["A"].tracer.sample_n = 1
        pub = await connect(brokers["A"], "pub")
        await pub.publish("t/x", b"m")
        await sub.next_message(timeout=5)
        adopted = brokers["B"].tracer.report()["entries"][0]
        assert adopted["e2e_ms"] < 40.0, adopted
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await close_cluster(brokers)


# ----------------------------------------------------------------------
# Federated metrics
# ----------------------------------------------------------------------


async def test_cluster_metrics_aggregation_and_endpoint():
    """Any node serves /cluster/metrics: peers' gossiped snapshots
    aggregate under node= labels, the page passes the Prometheus
    conformance checker, and the HTTP route works end to end."""
    checker = _load_script("check_metrics_exposition.py")
    brokers, mgrs = await make_cluster(PAIR,
                                       telemetry_interval_s=0.05)
    try:
        pub = await connect(brokers["B"], "pub")
        await pub.publish("warm/x", b"m")       # move B's counters
        await wait_for(lambda: "B" in mgrs["A"].telemetry.peers,
                       what="B snapshot gossiped to A")
        page = mgrs["A"].telemetry.cluster_exposition()
        assert checker.validate(page) == []
        assert 'maxmq_mqtt_messages_received{node="A"}' in page
        assert 'maxmq_mqtt_messages_received{node="B"}' in page
        assert 'maxmq_cluster_telemetry_age_seconds{node="B"}' in page

        reg = Registry()
        register_broker_metrics(reg, brokers["A"])
        srv = MetricsServer(
            "127.0.0.1:0", reg, tracer=brokers["A"].tracer,
            cluster_metrics=mgrs["A"].telemetry.cluster_exposition)
        srv.start()
        try:
            url = (f"http://127.0.0.1:{srv.bound_port}"
                   f"/cluster/metrics")
            loop = asyncio.get_running_loop()

            def get():
                with urllib.request.urlopen(url, timeout=5) as r:
                    return r.read().decode()

            body = await loop.run_in_executor(None, get)
            assert 'node="B"' in body
            # the local page grew the telemetry counter families too
            local = reg.expose()
            assert "maxmq_cluster_telemetry_snapshots_sent_total" \
                in local
            assert checker.validate(local) == []
        finally:
            srv.stop()
        await pub.disconnect()
    finally:
        await close_cluster(brokers)


async def test_telemetry_snapshot_cardinality_bound():
    """A hostile/buggy peer cannot grow a held snapshot past the
    cardinality bound, and out-of-order seqs are ignored."""
    brokers, mgrs = await make_cluster(PAIR)
    try:
        tel = mgrs["A"].telemetry
        tel.max_keys = 5

        class _Pkt:
            def __init__(self, payload: bytes) -> None:
                self.payload = payload

        big = {f"maxmq_fake_metric_{i:02d}": ["gauge", i]
               for i in range(20)}
        tel.handle_snapshot("B", ["$cluster", "telemetry", "Z"], _Pkt(
            json.dumps({"o": "Z", "s": 5, "full": 1,
                        "d": big}).encode()))
        assert len(tel.peers["Z"]["d"]) == 5
        # stale seq: ignored
        tel.handle_snapshot("B", ["$cluster", "telemetry", "Z"], _Pkt(
            json.dumps({"o": "Z", "s": 4, "full": 1,
                        "d": {"x": ["gauge", 1]}}).encode()))
        assert tel.snapshots_stale == 1
        assert len(tel.peers["Z"]["d"]) == 5
    finally:
        await close_cluster(brokers)


# ----------------------------------------------------------------------
# ADR-015 closure items
# ----------------------------------------------------------------------


async def test_qos2_release_leg_span():
    """The PUBREC->PUBREL release leg of a sampled QoS2 publish feeds
    the histogram-only ``release`` stage (previously on ADR-015's
    NOT-traced list)."""
    b = await make_node(trace_sample_n=1)
    try:
        sub = await connect(b, "s1")
        await sub.subscribe(("t/#", 2))
        pub = await connect(b, "p1")
        await pub.publish("t/x", b"m", qos=2, timeout=5)
        await wait_for(
            lambda: b.tracer.stage_hist["release"].count >= 1,
            what="release-leg span")
        assert b.tracer.stage_hist["release"].count >= 1
        # untracked pids leave nothing behind
        server_client = b.clients.get("p1")
        assert server_client._qos2_release_t0 == {}
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await b.close()


async def test_journal_bucket_attribution():
    """Group commits attribute their duration to each storage bucket
    the batch touched, exposed as the bucket-labelled histogram family
    (previously on ADR-015's NOT-traced list)."""
    checker = _load_script("check_metrics_exposition.py")
    store = WriteBehindStore(MemoryStore())
    b = await make_node(hooks=[StorageHook(store)], trace_sample_n=1)
    try:
        sub = await connect(b, "s1")
        await sub.subscribe(("t/#", 1))
        pub = await connect(b, "p1")
        await pub.publish("t/x", b"m", qos=1, retain=True, timeout=5)
        want = {"retained", "inflight", "clients", "sys_info"}
        await wait_for(lambda: set(b.tracer.journal_hist) & want,
                       what="journal bucket attribution")
        # boot-epoch bump commits under its own bucket too
        assert set(b.tracer.journal_hist) & want
        reg = Registry()
        register_broker_metrics(reg, b)
        page = reg.expose()
        assert "maxmq_storage_journal_commit_seconds_bucket{bucket=" \
            in page
        assert checker.validate(page) == []
        await pub.disconnect()
        await sub.disconnect()
    finally:
        await b.close()


# ----------------------------------------------------------------------
# Session-federation trace legs
# ----------------------------------------------------------------------


async def test_takeover_trace_and_sess_ship_report():
    """A sampled cross-node takeover produces a trace at the claimant
    whose entry gains the prior owner's ``sess_ship`` span report, and
    sampled QoS1 replication ops carry trace identity to the replica
    side."""
    brokers, mgrs = await make_cluster(PAIR)
    try:
        await wait_caps(mgrs)
        sess = MQTTClient(client_id="mov", version=5,
                          clean_start=False, session_expiry=3600)
        await sess.connect("127.0.0.1", brokers["A"].test_port)
        await sess.subscribe(("mv/#", 1))
        await wait_for(lambda: "mov" in mgrs["B"].sessions.ledger,
                       what="ledger replicated to B")

        # sampled QoS1 delivery: its replication op carries identity
        brokers["A"].tracer.sample_n = 1
        pub = await connect(brokers["A"], "pub")
        await pub.publish("mv/x", b"m", qos=1)
        await sess.next_message(timeout=5)
        await wait_for(
            lambda: mgrs["B"].sessions.trace_ops_applied >= 1,
            what="trace-tagged replication op applied at B")
        brokers["A"].tracer.sample_n = 0

        # epoch-fenced takeover at B, sampled there
        brokers["B"].tracer.sample_n = 1
        sess_b = MQTTClient(client_id="mov", version=5,
                            clean_start=False, session_expiry=3600)
        await sess_b.connect("127.0.0.1", brokers["B"].test_port)
        assert sess_b.session_present
        await wait_for(
            lambda: any("remote" in e and e["topic"].startswith(
                "$takeover/") for e in
                brokers["B"].tracer.report()["entries"]),
            what="sess_ship span report attached")
        entry = next(e for e in brokers["B"].tracer.report()["entries"]
                     if e["topic"] == "$takeover/mov")
        assert "takeover" in {s["stage"] for s in entry["spans"]}
        ship = entry["remote"][0]
        assert ship["node"] == "A"
        assert {s["stage"] for s in ship["spans"]} == {"sess_ship"}
        # sess reports must NOT pollute the publish per-hop e2e
        assert brokers["B"].tracer.cross_quantiles() == {}
        await sess_b.disconnect()
        await pub.disconnect()
    finally:
        await close_cluster(brokers)


# ----------------------------------------------------------------------
# $SYS health + bench-regression gate
# ----------------------------------------------------------------------


async def test_sys_cluster_health_subtree():
    brokers, mgrs = await make_cluster(PAIR)
    try:
        await wait_for(lambda: mgrs["A"].links["B"].connected,
                       what="link up")
        entries = brokers["A"]._sys_cluster_entries()
        base = "$SYS/broker/cluster/health/B"
        assert entries[f"{base}/state"] == 1
        assert entries[f"{base}/last_seen_s"] >= 0
        assert f"{base}/skew_ms" in entries
        assert f"{base}/queue_bytes" in entries
        assert f"{base}/route_lag" in entries
        assert f"{base}/sess_lag" in entries
    finally:
        await close_cluster(brokers)


def test_checker_self_test_covers_new_families():
    """The CI self-test page now exercises the ADR-017 families and
    folds /cluster/metrics findings into the exit code."""
    checker = _load_script("check_metrics_exposition.py")
    page = checker.self_test()
    assert "maxmq_storage_journal_commit_seconds" in page
    assert "maxmq_cluster_publish_e2e_seconds" in page
    assert "maxmq_cluster_telemetry_peers_held" in page
    assert "maxmq_broker_trace_adopted_total 1" in page
    assert "CLUSTER-PAGE-FINDING" not in page
    assert checker.validate(page) == []
