"""ADR 021 worker-shard e2e: the SO_REUSEPORT pool as an in-box
cluster, exercised through the REAL process boundary where it matters.

Four angles from the ISSUE-15 acceptance sheet:

* subprocess pool + SIGKILL — one worker dies mid-QoS1-stream; the
  client reconnects (the kernel re-shards the accept onto a sibling),
  resumes with session-present=1, and every PUBACKed payload is
  delivered (the replication barrier + shared journal at work)
* mixed pool+cluster composition — an external TCP node full-peered
  with the workers' unix mesh, one ``cluster_share_balance`` policy
  governing the pool AND cluster $share pick
* shared singletons — at workers=4 exactly ONE matcher-table compile
  (the sidecar) and ONE journal writer (the owner worker), asserted
  via the maxmq_matcher_*/maxmq_storage_* metric families, plus every
  worker showing up as a node in the /cluster/metrics exposition
* one correlated trace — a sampled cross-worker publish renders both
  workers' legs in a single /traces/chrome document

Single-core box: these assert semantics and invariants, never speedup.
"""

import asyncio
import contextlib
import os
import shutil
import signal
import socket
import tempfile
import time

import pytest

from maxmq_tpu.broker.workers import (await_routes, inprocess_pool,
                                      matcher_sock, run_pool, worker_sock)
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.utils.config import Config
from maxmq_tpu.utils.logger import new_logger


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def poll_until(pred, timeout: float = 10.0,
                     what: str = "condition") -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() >= deadline:
            raise TimeoutError(f"{what} never converged")
        await asyncio.sleep(0.02)


# -- subprocess pool plumbing ---------------------------------------------

def _worker_pids() -> list[int]:
    """PIDs of maxmq worker subprocesses the POOL PARENT (this test
    process) spawned."""
    me, out = os.getpid(), []
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            with open(f"/proc/{d}/cmdline", "rb") as f:
                cmd = f.read()
        except (OSError, ValueError, IndexError):
            continue
        if ppid == me and b"maxmq_tpu" in cmd:
            out.append(int(d))
    return out


def _owner_pid(client: MQTTClient, broker_port: int,
               pids: list[int]) -> int | None:
    """Which worker process holds the broker side of ``client``'s TCP
    connection (the kernel's SO_REUSEPORT pick): match the 4-tuple in
    /proc/net/tcp, then find the socket inode among the workers' fds."""
    lport = client.writer.get_extra_info("sockname")[1]
    inode = None
    with open("/proc/net/tcp") as f:
        for line in f.readlines()[1:]:
            parts = line.split()
            if (int(parts[1].split(":")[1], 16) == broker_port
                    and int(parts[2].split(":")[1], 16) == lport):
                inode = parts[9]
                break
    if inode is None:
        return None
    target = f"socket:[{inode}]"
    for pid in pids:
        with contextlib.suppress(OSError):
            for fd in os.listdir(f"/proc/{pid}/fd"):
                with contextlib.suppress(OSError):
                    if os.readlink(f"/proc/{pid}/fd/{fd}") == target:
                        return pid
    return None


@contextlib.asynccontextmanager
async def subprocess_pool(workers: int = 2, **conf_kw):
    """A REAL pool: parent in this process, workers as subprocesses
    sharing one SO_REUSEPORT TCP port. Yields (port, pool_dir)."""
    port = _free_port()
    tmp = tempfile.mkdtemp(prefix="maxmq-shard-")
    pool_dir = os.path.join(tmp, "mesh")
    conf = Config(workers=workers,
                  mqtt_tcp_address=f"127.0.0.1:{port}",
                  mqtt_unix_socket="", mqtt_sys_http_address="",
                  mqtt_sys_topic_interval=0, metrics_enabled=False,
                  matcher="trie", worker_link_dir=pool_dir,
                  log_format="json", log_level="error", **conf_kw)
    logger = new_logger(fmt="json", level="error")
    ready, stop = asyncio.Event(), asyncio.Event()
    task = asyncio.ensure_future(run_pool(conf, logger,
                                          ready=ready, stop=stop))
    try:
        await asyncio.wait_for(ready.wait(), 30)
        # serving point: every worker has bound its sibling-bridge
        # socket (created at serve, after the TCP listener)
        await poll_until(
            lambda: all(os.path.exists(worker_sock(pool_dir, i))
                        for i in range(workers)),
            timeout=30, what="worker boot")
        yield port, pool_dir
    finally:
        stop.set()
        with contextlib.suppress(asyncio.TimeoutError):
            await asyncio.wait_for(task, 30)
        shutil.rmtree(tmp, ignore_errors=True)


async def _connect_retry(client: MQTTClient, port: int,
                         timeout: float = 20.0) -> None:
    """Connect with retries: mid-respawn the kernel can briefly hand
    the accept to a worker that is still booting."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            await client.connect("127.0.0.1", port, timeout=5.0)
            return
        except Exception:
            if time.monotonic() >= deadline:
                raise
            await asyncio.sleep(0.2)


async def _publish_acked(port: int, pub_box: list, topic: str,
                         payload: bytes, acked: set) -> None:
    """QoS1 publish that survives its OWN worker dying: reconnect a
    fresh publisher and retry until the PUBACK lands."""
    for _ in range(40):
        try:
            await pub_box[0].publish(topic, payload, qos=1, timeout=5.0)
            acked.add(payload)
            return
        except Exception:
            with contextlib.suppress(Exception):
                await pub_box[0].close()
            pub_box[0] = MQTTClient("shard-pub")
            await _connect_retry(pub_box[0], port)
    raise AssertionError(f"publish {payload!r} never PUBACKed")


async def _settle(drain_once, acked: set, got: set,
                  timeout: float = 30.0) -> None:
    """Drain until every PUBACKed payload arrived (the macroday loss
    SLO: acked must become a subset of got)."""
    deadline = time.monotonic() + timeout
    while not acked <= got and time.monotonic() < deadline:
        await drain_once()
    assert acked <= got, f"PUBACKed loss: {sorted(acked - got)[:10]}"


async def test_worker_sigkill_takeover_e2e(tmp_path):
    """SIGKILL one worker mid-QoS1-stream: the subscriber reconnects
    onto a sibling with session-present=1 and zero PUBACKed loss —
    then a parked window (offline persistent session) drains back
    through the shared journal on the NEXT reconnect.

    Counted payloads follow the macroday loss SLO: a publish counts
    once routes are proven live from the publisher's worker (an
    uncounted warm publish delivered first), because a QoS1 PUBACK
    vouches for the subscriptions the accepting worker can SEE — the
    route-propagation window is the documented ADR-013 semantics, not
    loss."""
    async with subprocess_pool(
            2, storage_backend="sqlite",
            storage_path=str(tmp_path / "shard.db")) as (port, _pool):
        acked: set[bytes] = set()
        got: set[bytes] = set()
        pub_box = [MQTTClient("shard-pub")]
        await _connect_retry(pub_box[0], port)

        async def drain(client: MQTTClient, idle: float = 0.5) -> None:
            with contextlib.suppress(asyncio.TimeoutError):
                while True:
                    got.add(bytes((await client.next_message(
                        timeout=idle)).payload))

        async def warm_until_live(client: MQTTClient,
                                  tag: str) -> None:
            # uncounted probes until the route from the publisher's
            # CURRENT worker to the (re)claimed session is live
            before, i = len(got), 0
            while len(got) == before:
                await _publish_acked(port, pub_box, "shard/q1",
                                     f"{tag}-{i}".encode(), set())
                i += 1
                await drain(client, idle=0.3)
                assert i < 100, f"{tag}: delivery never started"

        sub = MQTTClient("shard-sub", version=5, clean_start=False,
                         session_expiry=600)
        await _connect_retry(sub, port)
        await sub.subscribe(("shard/q1", 1))
        await warm_until_live(sub, "warm")

        for i in range(15):                       # pre-kill stream
            await _publish_acked(port, pub_box, "shard/q1",
                                 f"pre-{i}".encode(), acked)
        await drain(sub)

        pids = _worker_pids()
        assert len(pids) == 2, pids
        victim = _owner_pid(sub, port, pids)
        assert victim is not None, "could not map subscriber to worker"
        os.kill(victim, signal.SIGKILL)           # mid-stream crash
        await sub.wait_closed(timeout=15)

        # kernel re-shards the accept onto the sibling (or the
        # respawned worker); the epoch-fenced claim restores the session
        sub2 = MQTTClient("shard-sub", version=5, clean_start=False,
                          session_expiry=600)
        await _connect_retry(sub2, port)
        assert sub2.session_present, \
            "takeover lost the session (session-present=0)"
        await warm_until_live(sub2, "rewarm")

        for i in range(10):                       # post-takeover stream
            await _publish_acked(port, pub_box, "shard/q1",
                                 f"post-{i}".encode(), acked)

        await _settle(lambda: drain(sub2, idle=1.0), acked, got)

        # parked window: the persistent session goes offline, the
        # stream keeps getting PUBACKed — each ack carries the
        # replication + shared-journal barrier — and the next claim
        # drains it all back
        await sub2.disconnect()
        for i in range(10):
            await _publish_acked(port, pub_box, "shard/q1",
                                 f"park-{i}".encode(), acked)
        sub3 = MQTTClient("shard-sub", version=5, clean_start=False,
                          session_expiry=600)
        await _connect_retry(sub3, port)
        assert sub3.session_present
        await _settle(lambda: drain(sub3, idle=1.0), acked, got)
        await sub3.disconnect()
        await pub_box[0].disconnect()

test_worker_sigkill_takeover_e2e._async_timeout = 180


# -- mixed pool + cluster composition -------------------------------------

async def test_mixed_pool_cluster_share_composition(tmp_path):
    """One ``cluster_share_balance`` policy governs the $share pick
    across pool workers AND an external cluster node (full peering:
    the external node lists each worker id as a peer)."""
    from maxmq_tpu.broker import (Broker, BrokerOptions, Capabilities,
                                  TCPListener)
    from maxmq_tpu.cluster import ClusterManager, PeerSpec
    from maxmq_tpu.hooks import AllowHook

    link_dir = str(tmp_path / "mesh")
    ext = Broker(BrokerOptions(capabilities=Capabilities(
        sys_topic_interval=0)))
    ext.add_hook(AllowHook())
    lst = ext.add_listener(TCPListener("t", "127.0.0.1:0"))
    await ext.serve()
    ext_port = lst._server.sockets[0].getsockname()[1]
    mgr = ClusterManager(
        ext, "C",
        [PeerSpec(f"A.w{i}", "", 0, path=worker_sock(link_dir, i))
         for i in range(2)],
        keepalive=1.0, share_balance="pin", session_sync="always")
    ext.attach_cluster(mgr)

    base = Config(cluster_node_id="A",
                  cluster_peers=f"C@127.0.0.1:{ext_port}",
                  cluster_share_balance="pin",
                  cluster_session_sync="always")
    key = ("g", "$share/g/mix/t")
    try:
        async with inprocess_pool(2, link_dir=link_dir,
                                  conf=base) as (brokers, ports):
            await mgr.start()
            ledgers = [b.cluster.routes.shares for b in brokers]
            ledgers.append(mgr.routes.shares)
            await poll_until(
                lambda: all(len(m.links) and all(
                    ln.connected for ln in m.links.values())
                    for m in [b.cluster for b in brokers] + [mgr]),
                timeout=15, what="mixed mesh")

            m0 = MQTTClient("mix-m0")
            await m0.connect("127.0.0.1", ports[0])
            await m0.subscribe("$share/g/mix/t", qos=0)
            mc = MQTTClient("mix-mc")
            await mc.connect("127.0.0.1", ext_port)
            await mc.subscribe("$share/g/mix/t", qos=0)
            await poll_until(
                lambda: all(set(led.members_for(key)) == {"A.w0", "C"}
                            for led in ledgers),
                timeout=15, what="mixed share ledger")

            pub = MQTTClient("mix-pub")
            await pub.connect("127.0.0.1", ports[1])
            await await_routes(brokers[1], "mix/t", n=2)
            n = 8
            for i in range(n):
                await pub.publish("mix/t", f"a{i}".encode())
            # pin balance: "A.w0" sorts below "C" -> the pool member
            # owns every pick, exactly once across the whole mesh
            await poll_until(lambda: m0.messages.qsize() >= n,
                             timeout=10, what="pool-owned delivery")
            await asyncio.sleep(0.3)
            assert m0.messages.qsize() == n
            assert mc.messages.qsize() == 0

            await m0.disconnect()   # pool member gone -> C owns
            await poll_until(
                lambda: all(led.members_for(key) == ["C"]
                            for led in ledgers),
                timeout=15, what="cession to the cluster node")
            for i in range(6):
                await pub.publish("mix/t", f"b{i}".encode())
            await poll_until(lambda: mc.messages.qsize() >= 6,
                             timeout=10, what="cluster-owned delivery")
            await asyncio.sleep(0.3)
            assert mc.messages.qsize() == 6
            await mc.disconnect()
            await pub.disconnect()
    finally:
        await ext.close()

test_mixed_pool_cluster_share_composition._async_timeout = 120


# -- shared singletons at workers=4 ---------------------------------------

async def test_pool_singletons_one_compile_one_journal(tmp_path):
    """workers=4 + sig matcher + sqlite storage: ONE table compile
    (the sidecar's engine factory runs once) and ONE journal writer
    (only the owner worker's registry exposes maxmq_storage_*), while
    every worker registers as a sidecar CLIENT and shows up as a node
    in the /cluster/metrics exposition."""
    from maxmq_tpu.matching.service import (MatcherService,
                                            attach_matcher_service)
    from maxmq_tpu.metrics import Registry, register_broker_metrics

    link_dir = str(tmp_path / "mesh")
    os.makedirs(link_dir, exist_ok=True)
    base = Config(matcher="sig", storage_backend="sqlite",
                  storage_path=str(tmp_path / "pool.db"),
                  cluster_telemetry_interval_s=0.2)

    compiles = []

    def counting_factory(index):
        from maxmq_tpu.matching.batcher import MicroBatcher
        from maxmq_tpu.matching.sig import SigEngine
        compiles.append(1)
        return MicroBatcher(SigEngine(index), window_us=200,
                            max_batch=256)

    svc = MatcherService(matcher_sock(link_dir),
                         engine_factory=counting_factory)
    await svc.start()
    try:
        async with inprocess_pool(4, link_dir=link_dir,
                                  conf=base) as (brokers, ports):
            for b in brokers:
                await attach_matcher_service(b, matcher_sock(link_dir))
            sub = MQTTClient("sg-sub")
            await sub.connect("127.0.0.1", ports[0])
            await sub.subscribe("sg/+/x")
            pub = MQTTClient("sg-pub")
            await pub.connect("127.0.0.1", ports[3])
            await await_routes(brokers[3], "sg/a/x")
            await pub.publish("sg/a/x", b"one-compile")
            m = await sub.next_message(5)
            assert m.payload == b"one-compile"

            assert len(compiles) == 1, \
                f"expected ONE table compile per box, got {len(compiles)}"
            assert svc.matches_served >= 1

            texts = []
            for b in brokers:
                reg = Registry()
                register_broker_metrics(reg, b)
                texts.append(reg.expose())
            journal_owners = [t for t in texts
                              if "maxmq_storage_boot_epoch" in t]
            assert len(journal_owners) == 1, \
                "exactly one journal writer per box"
            assert all("maxmq_matcher_service_reconnects_total" in t
                       for t in texts), "every worker is a sidecar client"

            # ADR 017: per-worker nodes in the federated exposition
            await poll_until(
                lambda: all(
                    f'node="w{i}"' in
                    brokers[0].cluster.telemetry.cluster_exposition()
                    for i in range(4)),
                timeout=15, what="/cluster/metrics per-worker nodes")
            await sub.disconnect()
            await pub.disconnect()
    finally:
        await svc.close()

test_pool_singletons_one_compile_one_journal._async_timeout = 120


# -- one correlated cross-worker trace ------------------------------------

async def test_cross_worker_trace_chrome():
    """A sampled publish crossing the worker mesh renders as ONE
    correlated /traces/chrome document: the remote worker's span
    report returns to the origin and lands on its own process row."""
    async with inprocess_pool(
            2, conf=Config(trace_sample_n=1)) as (brokers, ports):
        sub = MQTTClient("tr-sub")
        await sub.connect("127.0.0.1", ports[0])
        await sub.subscribe("tr/x")
        pub = MQTTClient("tr-pub")
        await pub.connect("127.0.0.1", ports[1])
        await await_routes(brokers[1], "tr/x")
        await pub.publish("tr/x", b"traced", qos=1)
        m = await sub.next_message(5)
        assert m.payload == b"traced"
        origin = brokers[1].tracer
        await poll_until(lambda: origin.remote_attached >= 1,
                         timeout=10, what="remote span return")
        doc = origin.chrome_events()
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M"}
        assert {"node w0", "node w1"} <= names, names
        assert any("@w0" in e["name"] for e in doc["traceEvents"]
                   if e.get("ph") == "X"), \
            "remote leg missing from the origin's chrome trace"
        await sub.disconnect()
        await pub.disconnect()

test_cross_worker_trace_chrome._async_timeout = 90


# -- 100K-connection soak (slow; env-scalable) ----------------------------

@pytest.mark.slow
@pytest.mark.timeout(960)
async def test_connection_soak():
    """ADR-021 soak on the macroday phase scheduler: a sharded box
    swallows a ramped connect flood with the ADR-012 connect-refusal
    and stall ladders ENGAGED, holds the fleet, and streams a tracked
    QoS1 sample through it — zero UNEXPLAINED loss. Target 100K where
    the fd budget allows; MAXMQ_SOAK_CONNECTIONS pins it."""
    from harness.macroday import ConnectionSoak

    sheet = await ConnectionSoak(workers=2).run()
    assert sheet["pass"], sheet["violations"]
    assert sheet["unexplained_connect_failures"] == 0
    assert sheet["unexplained_loss"] == 0

test_connection_soak._async_timeout = 900
