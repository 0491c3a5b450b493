"""Multi-core delivery: SO_REUSEPORT workers federated as an in-box
cluster (ADR 021, superseding the ADR-005 fan-out bus).

The reference gets per-connection parallelism for free — one goroutine
per client spread over every host core (vendor/github.com/mochi-co/
mqtt/v2/clients.go:190-202, server.go:221). An asyncio broker caps
per-message work (decode, QoS bookkeeping, encode, socket writes) on a
single core, so N worker processes each run the FULL broker for the
connections the kernel hands them (``SO_REUSEPORT`` shards accepts with
no parent in the accept path).

What changed in ADR 021: the workers no longer talk over a bespoke
fan-out bus with its own gossip/takeover frames. Each worker IS a
cluster node — ``w0..wN-1`` — meshed over unix-domain bridge links
(the ``local`` link flavor: connect-by-path, budget-exempt, skew
pinned to zero), so cross-worker publish forwarding, route-table
aggregation, retained convergence, epoch-fenced session takeover,
cluster-wide ``$share`` through the ShareLedger, and the ADR-018
``cluster_fwd_durability`` barriers are all the EXISTING ADR-013/016/
018 machinery, not a parallel implementation. What this module still
owns is process supervision (spawn, respawn-with-throttle, pool
metrics) and the per-worker config derivation.

Shared singletons per box (the perf point of ADR 021):

* ONE matcher sidecar — when the box config asks for a device engine
  (``matcher = "sig"``), the pool parent runs a
  :class:`~..matching.service.MatcherService` on a pool socket and
  every worker attaches as a ``matcher=service`` client behind its own
  ADR-011 supervisor. Table compiles happen once per box, and match
  requests from all workers coalesce into the same device
  micro-batches.
* ONE write-behind journal — only ``worker_journal_owner`` (default 0)
  keeps ``storage_backend``; the owner restores the cluster session
  buckets at boot and the ADR-016 claim path routes each session to
  whichever worker its client reconnects to. One fsync cadence per
  box, never N processes contending on one SQLite file.

Mixed pool+cluster composition: ``cluster_peers`` entries are appended
to EVERY worker's peer list (full peering), so an external node that
lists each worker as a peer composes with the mesh under one set of
``cluster_share_balance`` ownership rules. A remote box that only
knows a single node id cannot receive from workers it never listed —
see ADR 021's topology notes.

Scaling expectation: near-linear in delivery-bound workloads up to the
host's core count (this dev box has ONE core, so the functional tests
assert cross-worker semantics, not speedup — the ``cshard`` bench
config measures the real curve on multi-core hosts).
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import time

from .. import faults
from ..accel import DEVICE_MATCHERS

POOL_DIR_ENV = "MAXMQ_POOL_DIR"

# matcher engines the pool parent hoists into the shared sidecar; a
# box already on ``service`` points at an external sidecar, and the
# CPU trie stays per-worker (no chip to share)
_SIDECAR_MATCHERS = DEVICE_MATCHERS


def worker_sock(pool_dir: str, worker_id: int) -> str:
    """The unix-domain socket worker ``worker_id`` accepts sibling
    bridge links on."""
    return os.path.join(pool_dir, f"w{worker_id}.sock")


def matcher_sock(pool_dir: str) -> str:
    return os.path.join(pool_dir, "matcher.sock")


def worker_node_id(conf, worker_id: int) -> str:
    """Cluster node id of one worker: ``w<i>``, prefixed with the box's
    own cluster identity when it has one (so a mixed pool+cluster mesh
    stays globally unambiguous)."""
    base = conf.cluster_node_id
    return f"{base}.w{worker_id}" if base else f"w{worker_id}"


def worker_conf(conf, worker_id: int, pool_dir: str):
    """Derive worker ``worker_id``'s Config from the box config.

    The worker mesh is expressed entirely through the existing
    ``cluster_*`` surface: node id ``w<i>``, peers = every sibling over
    ``unix:`` links plus the box's external ``cluster_peers`` verbatim,
    session sync per ``worker_session_sync`` (default ``always`` — a
    SIGKILLed worker's sibling must redeliver every PUBACKed message).
    Singleton ownership: only ``worker_journal_owner`` keeps the
    storage backend, only worker 0 keeps the unshareable listeners
    (unix socket, $SYS HTTP) and the metrics address, and device
    matchers are rewritten to ``service`` against the shared sidecar.
    """
    siblings = ",".join(
        f"{worker_node_id(conf, j)}@unix:{worker_sock(pool_dir, j)}"
        for j in range(conf.workers) if j != worker_id)
    peers = ",".join(p for p in (siblings, conf.cluster_peers.strip(", "))
                     if p)
    kw = dict(cluster_node_id=worker_node_id(conf, worker_id),
              cluster_peers=peers,
              cluster_session_sync=conf.worker_session_sync)
    if worker_id != conf.worker_journal_owner:
        kw["storage_backend"] = ""
    if worker_id != 0:
        # SO_REUSEPORT shards the TCP/WS listeners; the unix-socket and
        # $SYS-HTTP listeners (and metrics) cannot share an address
        kw["mqtt_unix_socket"] = ""
        kw["mqtt_sys_http_address"] = ""
    if conf.matcher in _SIDECAR_MATCHERS:
        kw["matcher"] = "service"
        kw["matcher_socket"] = matcher_sock(pool_dir)
    return dataclasses.replace(conf, **kw)


def _tune_local_links(manager, conf) -> None:
    """Apply the ``worker_link_*`` knobs to the loopback links ONLY —
    a mixed box's external TCP links keep the ``cluster_link_*``
    budget/keepalive they were built with."""
    if manager is None:
        return
    for link in manager.links.values():
        if link.local:
            link.byte_budget = conf.worker_link_byte_budget
            link.keepalive = float(conf.worker_link_keepalive)


def build_worker_broker(wconf, logger, worker_id: int, pool_dir: str):
    """One worker's broker: the standard bootstrap build (so cluster,
    storage, tracing, and overload wiring are production-parity) plus
    the sibling-bridge unix listener every peer worker dials."""
    from ..bootstrap import build_broker
    from .listeners import UnixListener

    broker = build_broker(wconf, logger)
    path = worker_sock(pool_dir, worker_id)
    with contextlib.suppress(OSError):
        os.unlink(path)     # stale socket from a crashed incarnation
    broker.add_listener(UnixListener("peer-bridge", path))
    _tune_local_links(broker.cluster, wconf)
    return broker


async def run_worker(conf, logger, worker_id: int, pool_dir: str,
                     ready: asyncio.Event | None = None,
                     stop: asyncio.Event | None = None) -> None:
    """One pool worker process: derive the worker config, run the full
    broker with its sibling mesh, serve until stopped."""
    from ..bootstrap import _maybe_attach_service, build_metrics

    wconf = worker_conf(conf, worker_id, pool_dir)
    broker = build_worker_broker(wconf, logger, worker_id, pool_dir)
    # service matcher attaches BEFORE metrics so the matcher families
    # register (same ordering contract as run_server)
    await _maybe_attach_service(wconf, broker)
    metrics = build_metrics(wconf, broker, logger) if worker_id == 0 else None
    await broker.serve()
    if metrics is not None:
        metrics.start()
    logger.with_prefix("worker").info("pool worker started",
                                      worker=worker_id,
                                      node=wconf.cluster_node_id)
    if ready is not None:
        ready.set()
    if stop is None:
        stop = asyncio.Event()
        import signal
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:
                pass
    if faults.fire(faults.POOL_WORKER):
        # injected worker death (ADR 011 fault suite; armed through the
        # MAXMQ_FAULTS env the pool parent propagates): exit now so the
        # parent's supervision loop observes the crash and respawns us
        stop.set()
    try:
        await stop.wait()
    finally:
        await broker.close()
        if metrics is not None:
            metrics.stop()
        matcher = broker.matcher
        if matcher is not None and hasattr(matcher, "close"):
            await matcher.close()
        with contextlib.suppress(OSError):
            os.unlink(worker_sock(pool_dir, worker_id))


class PoolStats:
    """Supervision counters for one pool parent, exported as the
    ``maxmq_pool_*`` family (metrics.register_pool_metrics)."""

    def __init__(self) -> None:
        self.worker_restarts = 0


# process-wide default (one pool parent per process); tests construct
# their own and pass it to _supervise_workers
POOL_STATS = PoolStats()


async def _supervise_workers(procs, spawn, boot, stats: PoolStats = None,
                             interval: float = 2.0) -> None:
    """A worker that dies (crash, OOM kill, injected fault) is logged,
    counted (stats.worker_restarts -> maxmq_pool_worker_restarts_total),
    and respawned — the pool must not silently degrade to N-1.
    Throttled per slot so a crash loop can't fork-bomb the host. The
    respawned incarnation re-binds its SO_REUSEPORT share and its
    sibling bridge socket; peers reconnect through the local links'
    fast backoff and re-exchange routes/sessions (epoch-fenced, so the
    dead incarnation's state flushes on arrival)."""
    stats = stats if stats is not None else POOL_STATS
    last_spawn = [0.0] * len(procs)
    while True:
        await asyncio.sleep(interval)
        for i, p in enumerate(procs):
            rc = p.poll()
            if rc is None:
                continue
            wait = max(0.0, 5.0 - (time.monotonic() - last_spawn[i]))
            boot.error("pool worker exited; restarting", worker=i,
                       rc=rc, backoff_s=round(wait, 1))
            if wait:
                await asyncio.sleep(wait)
            last_spawn[i] = time.monotonic()
            procs[i] = spawn(i)
            stats.worker_restarts += 1


async def await_mesh(brokers, timeout: float = 10.0) -> None:
    """Wait until every worker's link to every sibling is connected —
    the pool's "serving" point. Route/session exchange starts at each
    link-up, so callers that need a specific filter visible on a
    specific worker poll :func:`await_routes` after subscribing."""
    deadline = time.monotonic() + timeout
    while True:
        down = [(b.cluster.node_id, peer)
                for b in brokers
                for peer, link in b.cluster.links.items()
                if link.local and not link.connected]
        if not down:
            return
        if time.monotonic() >= deadline:
            raise TimeoutError(f"worker mesh not converged: {down}")
        await asyncio.sleep(0.01)


async def await_routes(broker, topic: str, n: int = 1,
                       timeout: float = 5.0) -> None:
    """Poll until ``broker``'s route table forwards ``topic`` to at
    least ``n`` peers. Publish forwarding is route-driven (unlike the
    ADR-005 bus, which broadcast blindly), so a subscribe on one worker
    is visible to a publisher on another only after the route
    advertisement lands — tests hop this barrier explicitly instead of
    sleeping."""
    deadline = time.monotonic() + timeout
    while len(broker.cluster.routes.nodes_for(topic)) < n:
        if time.monotonic() >= deadline:
            raise TimeoutError(f"route for {topic!r} never reached "
                               f"{broker.cluster.node_id}")
        await asyncio.sleep(0.01)


@contextlib.asynccontextmanager
async def inprocess_pool(n: int = 2, link_dir: str | None = None,
                         conf=None, converge: bool = True):
    """N pool workers in ONE process: the same build_worker_broker
    wiring the subprocess pool runs — per-worker ClusterManager, unix
    mesh links, shared-singleton config derivation — minus the process
    boundary (which only the kernel's SO_REUSEPORT accept sharding
    cares about; here each worker binds its own ephemeral port so
    tests can target a specific worker). Yields (brokers, ports).
    Used by the cross-worker test suite and the overhead measurement
    harness (tools/measure_pool.py); also the embedding surface for
    hosts that want a pool without subprocesses."""
    from ..utils.config import Config
    from ..utils.logger import new_logger

    link_dir = link_dir or f"/tmp/maxmq-pool-inproc-{os.getpid()}"
    os.makedirs(link_dir, exist_ok=True)
    base = dataclasses.replace(
        conf or Config(), workers=n,
        mqtt_tcp_address="127.0.0.1:0", mqtt_unix_socket="",
        mqtt_sys_http_address="", mqtt_sys_topic_interval=0,
        metrics_enabled=False)
    logger = new_logger(fmt="json", level="error")
    brokers, ports = [], []
    try:
        for i in range(n):
            wconf = worker_conf(base, i, link_dir)
            b = build_worker_broker(wconf, logger, i, link_dir)
            await b.serve()
            brokers.append(b)
            lst = b.listeners.get("tcp")
            ports.append(lst._server.sockets[0].getsockname()[1])
        if converge:
            await await_mesh(brokers)
        yield brokers, ports
    finally:
        for b in brokers:
            await b.close()
        for i in range(n):
            with contextlib.suppress(OSError):
                os.unlink(worker_sock(link_dir, i))


def _engine_factory(conf):
    """The sidecar's engine build: bootstrap.build_engine (the sig
    engine, mesh-sharded when configured) behind a MicroBatcher — the
    ONE table compile per box the workers share."""
    def factory(index):
        from ..bootstrap import build_engine
        from ..matching.batcher import MicroBatcher
        return MicroBatcher(build_engine(conf, index),
                            window_us=conf.matcher_batch_window_us,
                            max_batch=conf.matcher_max_batch)
    return factory


async def _maybe_pool_matcher_service(conf, pool_dir: str):
    """ADR 021: one chip-owning matcher sidecar per box. The parent
    owns it (accelerator runtimes are single-claim — N workers cannot
    each hold the device), workers attach as ``matcher=service``
    clients behind their own ADR-011 supervisors, so a sidecar crash
    degrades every worker to its CPU trie and the reconnect ladder
    reseeds — never a pool-wide wedge."""
    if conf.matcher not in _SIDECAR_MATCHERS:
        return None
    from ..matching.service import MatcherService
    svc = MatcherService(matcher_sock(pool_dir),
                         engine_factory=_engine_factory(conf))
    await svc.start()
    return svc


def _worker_spawner(env: dict):
    """Build the pool's spawn(i) closure, scoping pool.worker faults
    (ADR 011 drills) to mean "kill A worker", not "kill every worker
    forever": MAXMQ_FAULTS is parsed at import in EACH subprocess, so
    an unscoped spec would re-arm in all N workers AND in every
    respawned replacement — a throttled permanent crash loop instead
    of a kill-once/recover drill. The first spawn keeps the
    pool.worker entries; every other spawn (other slots, and all
    respawns) gets them stripped."""
    fault_spec = env.get("MAXMQ_FAULTS", "")
    entries = [e.strip() for e in fault_spec.split(",") if e.strip()]
    kept = ",".join(e for e in entries
                    if not e.startswith(faults.POOL_WORKER))
    has_kill = any(e.startswith(faults.POOL_WORKER) for e in entries)
    delivered = [not has_kill]    # nothing to scope -> strip never

    def spawn(i: int):
        wenv = dict(env)
        wenv["MAXMQ_WORKER_ID"] = str(i)
        if fault_spec and delivered[0]:
            if kept:
                wenv["MAXMQ_FAULTS"] = kept
            else:
                wenv.pop("MAXMQ_FAULTS", None)
        delivered[0] = True
        return subprocess.Popen(
            [sys.executable, "-m", "maxmq_tpu", "start", "--no-banner"],
            env=wenv)

    return spawn


async def run_pool(conf, logger, ready: asyncio.Event | None = None,
                   stop: asyncio.Event | None = None) -> None:
    """The pool parent: shared matcher sidecar + N worker subprocesses
    + supervision. The parent never touches a client socket — the
    kernel (SO_REUSEPORT) shards accepts directly onto the workers —
    and (since ADR 021) never relays a message either: the workers
    mesh directly over their unix bridge sockets."""
    from ..utils.config import config_as_dict

    boot = logger.with_prefix("pool")
    pool_dir = conf.worker_link_dir or f"/tmp/maxmq-pool-{os.getpid()}"
    os.makedirs(pool_dir, exist_ok=True)
    service = await _maybe_pool_matcher_service(conf, pool_dir)

    env = dict(os.environ)
    env[POOL_DIR_ENV] = pool_dir
    env["MAXMQ_POOL_CONF"] = json.dumps(config_as_dict(conf))
    spawn = _worker_spawner(env)

    procs = [spawn(i) for i in range(conf.workers)]
    stats = PoolStats()
    metrics = None
    if conf.pool_metrics_address:
        # parent-side supervision metrics (worker 0 owns the broker
        # metrics address, so the pool family gets its own endpoint)
        from ..metrics import MetricsServer, Registry, register_pool_metrics
        registry = Registry()
        register_pool_metrics(registry, stats)
        metrics = MetricsServer(conf.pool_metrics_address, registry,
                                path=conf.metrics_path,
                                logger=logger.with_prefix("pool-metrics"))
        metrics.start()
    boot.info("worker pool started", workers=conf.workers,
              pool_dir=pool_dir, tcp=conf.mqtt_tcp_address,
              matcher_sidecar=bool(service))
    if ready is not None:
        ready.set()
    if stop is None:
        stop = asyncio.Event()
        import signal
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:
                pass

    watcher = asyncio.get_running_loop().create_task(
        _supervise_workers(procs, spawn, boot, stats=stats))
    try:
        await stop.wait()
    finally:
        watcher.cancel()
        if metrics is not None:
            metrics.stop()
        boot.info("shutting down worker pool")
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if service is not None:
            await service.close()
        for i in range(conf.workers):
            with contextlib.suppress(OSError):
                os.unlink(worker_sock(pool_dir, i))
        with contextlib.suppress(OSError):
            os.rmdir(pool_dir)
