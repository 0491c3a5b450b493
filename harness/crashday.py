"""ADR 024: the "crashday" kill-point crash scenario.

MacroDay (ADR 020) kills whole processes at arbitrary instants;
CrashDay kills them at NAMED instants in the commit pipeline — the
``crash.at`` points (faults.CRASH_POINTS) a subprocess broker SIGKILLs
itself at — and machine-checks the durability contract ADR 014 only
documented:

* ``storage_sync=always``  — ZERO PUBACKed loss, across every sampled
  kill point (pre-fsync, post-fsync-pre-ack-release, mid-WAL-write,
  mid-restore-parse). The acked ledger at each death is exactly the
  redelivery obligation of the next boot.
* ``storage_sync=batched`` — measured loss per crash bounded by the
  configured ``batch_ms``/``batch_ops`` window (the documented window,
  now asserted).
* QoS2 — no payload delivered twice across any crash.
* torn tails — truncating the WAL's final bytes (power-loss torn
  write) plus hand-torn records still boots to SERVING, with exact
  quarantine accounting (one quarantine row per bad record).
* recovery time — spawn→accepting for every post-crash boot, scored
  against an SLO bound.

Degrade phases (no kill — the disk fails, the broker must NOT):

* ``enospc`` — every commit returns ENOSPC: the breaker opens
  immediately, QoS0-irrelevant rewrites shed, acks keep flowing
  (ADR-011 availability over durability), counters fire.
* ``fsync``  — fsync failures poison the backend: breaker trips, the
  connection reopens on reprobe, the parked journal replays, and the
  broker recovers to a closed breaker while still serving.

Every broker is a REAL subprocess running the production bootstrap
(run_server) configured purely through MAXMQ_* env; crash points and
disk faults arm through the MAXMQ_FAULTS rail the subprocess parses at
import. The scenario emits one machine-checkable SLO sheet
(``sheet["pass"]`` + violations).

``python -m harness.crashday --smoke`` runs the <60s smoke shape
(3 kill points, tmpfs store) the tier-1 suite wires in.
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import shutil
import signal
import socket
import sqlite3
import subprocess
import sys
import tempfile
import time
import urllib.request

from maxmq_tpu.hooks.faultstore import torn_tail
from maxmq_tpu.mqtt_client import MQTTClient

# the kill points a single-node day samples; replica_flush needs a
# cluster under it and is exercised by the unit tier instead
KILL_POINTS = ("pre_fsync", "post_fsync_pre_ack", "mid_wal_write",
               "restore_parse")

BROKER_SCRIPT = """
import asyncio, os
from maxmq_tpu.bootstrap import new_logger_from_config, run_server
from maxmq_tpu.utils.config import load_config
conf = load_config(path=None, env=os.environ)
asyncio.run(run_server(conf, new_logger_from_config(conf)))
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _store_root() -> str:
    """tmpfs when the box has one — the day measures the PIPELINE's
    crash behavior, not the benchmark disk's seek time."""
    for p in ("/dev/shm", tempfile.gettempdir()):
        if os.path.isdir(p):
            return p
    return tempfile.gettempdir()


def _scrape(port: int) -> dict[str, float]:
    """One /metrics scrape flattened to {name: value} (labels
    stripped; last sample of a name wins — good enough for the
    unlabeled storage/overload families the sheet reads)."""
    out: dict[str, float] = {}
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5.0) as resp:
        for line in resp.read().decode().splitlines():
            if not line or line.startswith("#"):
                continue
            parts = line.split()
            if len(parts) != 2:
                continue
            name = parts[0].partition("{")[0]
            try:
                out[name] = float(parts[1])
            except ValueError:
                continue
    return out


class CrashDay:
    """One crash day against one store file. ``run()`` returns the
    SLO sheet."""

    def __init__(self, *, policy: str = "always", kills: int = 20,
                 msgs_per_cycle: int = 30, drain_every: int = 5,
                 batch_ms: int = 100, batch_ops: int = 256,
                 slo_recovery_ms: float = 20000.0,
                 store_dir: str | None = None, seed: int = 20240,
                 smoke: bool = False) -> None:
        if smoke:
            kills = min(kills, 3)
            msgs_per_cycle = min(msgs_per_cycle, 12)
            drain_every = min(drain_every, 3)
        self.policy = policy
        self.kills = kills
        self.msgs_per_cycle = msgs_per_cycle
        self.drain_every = max(drain_every, 1)
        self.batch_ms = batch_ms
        self.batch_ops = batch_ops
        self.slo_recovery_ms = slo_recovery_ms
        self.smoke = smoke
        self.rng = random.Random(seed)
        self._own_dir = store_dir is None
        self.dir = store_dir or tempfile.mkdtemp(
            prefix="crashday-", dir=_store_root())
        self.port = _free_port()
        self.sheet: dict = {"config": "crashday", "policy": policy,
                            "kills": kills, "kill_points": {},
                            "phases": []}
        # ledgers: payload -> acked at which cycle; delivered multiset
        self.acked_q1: dict[bytes, int] = {}
        self.acked_q2: dict[bytes, int] = {}
        self.acked_order: dict[int, list[bytes]] = {}  # ack sequence
        self.got: dict[bytes, int] = {}
        self.cycle_rate: dict[int, float] = {}   # acked msgs/s per cycle
        self._procs: list[subprocess.Popen] = []

    # ------------------------------------------------------------------
    # subprocess broker management
    # ------------------------------------------------------------------

    def _spawn(self, db: str, *, faults_spec: str = "",
               metrics_port: int = 0, sync: str | None = None,
               backoff_s: float = 0.2) -> subprocess.Popen:
        env = dict(os.environ)
        env["PYTHONPATH"] = _repo_root() + os.pathsep \
            + env.get("PYTHONPATH", "")
        env.update(
            MAXMQ_MQTT_TCP_ADDRESS=f"127.0.0.1:{self.port}",
            MAXMQ_STORAGE_BACKEND="sqlite",
            MAXMQ_STORAGE_PATH=db,
            MAXMQ_STORAGE_SYNC=sync or self.policy,
            MAXMQ_STORAGE_BATCH_MS=str(self.batch_ms),
            MAXMQ_STORAGE_BATCH_OPS=str(self.batch_ops),
            MAXMQ_STORAGE_BREAKER_BACKOFF_S=str(backoff_s),
            MAXMQ_STORAGE_BREAKER_BACKOFF_MAX_S="1.0",
            MAXMQ_MATCHER="trie",
            MAXMQ_MQTT_SYS_TOPIC_INTERVAL="0",
            MAXMQ_LOG_LEVEL="error",
            JAX_PLATFORMS="cpu",
        )
        if metrics_port:
            env["MAXMQ_METRICS_ENABLED"] = "true"
            env["MAXMQ_METRICS_ADDRESS"] = f"127.0.0.1:{metrics_port}"
        else:
            env["MAXMQ_METRICS_ENABLED"] = "false"
        if faults_spec:
            env["MAXMQ_FAULTS"] = faults_spec
        else:
            env.pop("MAXMQ_FAULTS", None)
        proc = subprocess.Popen([sys.executable, "-c", BROKER_SCRIPT],
                                env=env, cwd=self.dir)
        self._procs.append(proc)
        return proc

    async def _wait_ready_or_death(self, proc: subprocess.Popen,
                                   timeout: float = 45.0) -> bool:
        """True once the broker accepts, False when it died first (a
        restore-parse kill dies DURING boot — that is the drill)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                return False
            try:
                _r, w = await asyncio.open_connection("127.0.0.1",
                                                      self.port)
                w.close()
                return True
            except OSError:
                await asyncio.sleep(0.05)
        raise AssertionError("broker neither served nor died in "
                             f"{timeout:.0f}s")

    def _kill(self, proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)

    def _settle_s(self) -> float:
        """Grace before an EXTERNAL kill of a healthy broker: long
        enough for the journal to commit everything already acked
        (always drains eagerly; batched needs its window)."""
        return max(0.5, 3.0 * self.batch_ms / 1000.0)

    # ------------------------------------------------------------------
    # traffic
    # ------------------------------------------------------------------

    async def _setup_subscriber(self) -> None:
        sub = MQTTClient(client_id="cd-sub", clean_start=False)
        await sub.connect("127.0.0.1", self.port)
        await sub.subscribe(("cd/q1/#", 1), ("cd/q2/#", 2))
        await sub.disconnect()

    async def _stream_until_death(self, proc: subprocess.Popen,
                                  cycle: int) -> int:
        """PUBACK/PUBCOMP-paced QoS1+QoS2 stream into the durable
        subscriber's topics until the broker dies (the armed crash
        point) or the cycle budget runs out. Returns acked count."""
        pub = MQTTClient(client_id=f"cd-pub-{cycle}")
        try:
            await pub.connect("127.0.0.1", self.port)
        except OSError:
            return 0                      # died between ready and here
        acked = 0
        t0 = time.perf_counter()
        try:
            for i in range(self.msgs_per_cycle):
                qos2 = (i % 3 == 2)
                payload = (f"c{cycle}-{'q2' if qos2 else 'q1'}-{i}"
                           .encode())
                topic = "cd/q2/t" if qos2 else "cd/q1/t"
                try:
                    await pub.publish(topic, payload, qos=2 if qos2
                                      else 1, timeout=5.0)
                except Exception:
                    break                 # broker died mid-flight
                ledger = self.acked_q2 if qos2 else self.acked_q1
                ledger[payload] = cycle
                self.acked_order.setdefault(cycle, []).append(payload)
                acked += 1
                if proc.poll() is not None:
                    break
        finally:
            await pub.close()
        dur = max(time.perf_counter() - t0, 1e-6)
        self.cycle_rate[cycle] = acked / dur
        return acked

    async def _drain(self, expect_session: bool = True) -> int:
        """Resume the durable subscriber and take everything the broker
        owes it; idle-quiesce so QoS2 handshakes complete before the
        disconnect (a half-open window would re-send next time)."""
        sub = MQTTClient(client_id="cd-sub", clean_start=False)
        await sub.connect("127.0.0.1", self.port)
        if expect_session and not sub.connack.session_present:
            self.sheet.setdefault("session_losses", 0)
            self.sheet["session_losses"] += 1
        n = 0
        idle = 2.0
        while True:
            try:
                m = await sub.next_message(timeout=idle)
            except asyncio.TimeoutError:
                break
            self.got[m.payload] = self.got.get(m.payload, 0) + 1
            n += 1
            idle = 1.0
        await sub.disconnect()
        return n

    # ------------------------------------------------------------------
    # phases
    # ------------------------------------------------------------------

    async def _phase_kill_cycles(self) -> None:
        db = os.path.join(self.dir, "crashday.db")
        t0 = time.perf_counter()
        # setup boot: durable subscriber session, no faults
        proc = self._spawn(db)
        assert await self._wait_ready_or_death(proc)
        await self._setup_subscriber()
        await asyncio.sleep(self._settle_s())
        self._kill(proc)

        recovery_ms: list[float] = []
        external = boot_deaths = 0
        # every point gets floor(kills/len) guaranteed draws, the
        # remainder is sampled — coverage by construction, not luck
        points = list(KILL_POINTS) * (self.kills // len(KILL_POINTS))
        while len(points) < self.kills:
            points.append(self.rng.choice(KILL_POINTS))
        self.rng.shuffle(points)
        for cycle in range(1, self.kills + 1):
            point = points[cycle - 1]
            # skip counts pipeline hits for the site: commits for the
            # journal points, per-op for mid_wal_write, per-record for
            # restore_parse — sampled so crashes land at varied depths.
            # `always` commits once per PUBACK-paced publish; `batched`
            # commits once per window, so its skips must stay shallow
            # or the kill outlives the cycle's traffic entirely
            if self.policy == "always":
                skip = self.rng.randrange(1, 4 + self.msgs_per_cycle // 2)
            else:
                skip = self.rng.randrange(1, 5)
            spec = f"crash.at#{point}:kill:1:0:{skip}"
            self.sheet["kill_points"][point] = \
                self.sheet["kill_points"].get(point, 0) + 1
            spawn_t = time.perf_counter()
            proc = self._spawn(db, faults_spec=spec)
            if await self._wait_ready_or_death(proc):
                recovery_ms.append(
                    (time.perf_counter() - spawn_t) * 1e3)
                await self._stream_until_death(proc, cycle)
                # a just-fired SIGKILL needs a beat before poll() sees
                # it — don't misread a landed crash as an external kill
                deadline = time.monotonic() + 2.0
                while (proc.poll() is None
                        and time.monotonic() < deadline):
                    await asyncio.sleep(0.05)
                if proc.poll() is None:
                    # the sampled skip outlived the cycle's traffic:
                    # the kill happens anyway, from outside
                    await asyncio.sleep(self._settle_s())
                    external += 1
                self._kill(proc)
            else:
                boot_deaths += 1          # died mid-restore: the drill
            if cycle % self.drain_every == 0:
                proc = self._spawn(db)
                spawn_t = time.perf_counter()
                assert await self._wait_ready_or_death(proc)
                recovery_ms.append(
                    (time.perf_counter() - spawn_t) * 1e3)
                await self._drain()
                await asyncio.sleep(self._settle_s())
                self._kill(proc)
        # final boot + full drain
        proc = self._spawn(db)
        spawn_t = time.perf_counter()
        assert await self._wait_ready_or_death(proc)
        recovery_ms.append((time.perf_counter() - spawn_t) * 1e3)
        await self._drain()
        await asyncio.sleep(self._settle_s())
        self._kill(proc)

        recovery_ms.sort()
        s = self.sheet
        s["external_kills"] = external
        s["boot_deaths"] = boot_deaths
        s["serving_boots"] = len(recovery_ms)
        if recovery_ms:
            s["recovery_p99_ms"] = round(
                recovery_ms[min(len(recovery_ms) - 1,
                                int(len(recovery_ms) * 0.99))], 1)
            s["recovery_max_ms"] = round(recovery_ms[-1], 1)
        s["phases"].append({"name": "kill_cycles",
                            "dur_s": round(time.perf_counter() - t0, 3)})

    async def _phase_torn_tail(self) -> None:
        """Power-loss torn write: SIGKILL mid-traffic, truncate the
        WAL tail AND plant unparseable records in every bucket; the
        next boot must SERVE with exactly one quarantine row per bad
        record."""
        t0 = time.perf_counter()
        db = os.path.join(self.dir, "torn.db")
        proc = self._spawn(db, sync="always")
        assert await self._wait_ready_or_death(proc)
        sub = MQTTClient(client_id="torn-sub", clean_start=False)
        await sub.connect("127.0.0.1", self.port)
        await sub.subscribe(("torn/#", 1))
        await sub.disconnect()
        pub = MQTTClient(client_id="torn-pub")
        await pub.connect("127.0.0.1", self.port)
        for i in range(12):
            await pub.publish(f"torn/r{i}", f"keep-{i}".encode(),
                              qos=1, retain=True, timeout=5.0)
        await pub.close()
        self._kill(proc)                  # mid-day, zero grace
        cut = torn_tail(db, 512, target="wal")
        planted = []
        conn = sqlite3.connect(db)
        for n, bucket in enumerate(("clients", "subscriptions",
                                    "retained", "inflight")):
            key = f"torn|{n}"
            conn.execute(
                "INSERT OR REPLACE INTO kv (bucket, key, value) "
                "VALUES (?, ?, ?)",
                (bucket, key, '{"torn": tru'))
            planted.append(f"{bucket}|{key}")
        conn.commit()
        conn.close()
        proc = self._spawn(db, sync="always")
        serving = await self._wait_ready_or_death(proc)
        await asyncio.sleep(self._settle_s())  # quarantine rewrites
        self._kill(proc)
        rows = {}
        if serving:
            conn = sqlite3.connect(db)
            rows = dict(conn.execute(
                "SELECT key, value FROM kv WHERE bucket=?",
                ("quarantine",)).fetchall())
            conn.close()
        self.sheet["torn"] = {
            "wal_cut_bytes": cut,
            "planted": len(planted),
            "quarantined": sum(1 for k in planted if k in rows),
            "quarantine_rows": len(rows),
            "boot_serving": bool(serving),
        }
        self.sheet["phases"].append(
            {"name": "torn_tail",
             "dur_s": round(time.perf_counter() - t0, 3)})

    async def _phase_enospc(self) -> None:
        """Disk full, forever: the broker must keep serving — acks
        flow degraded, the breaker opens immediately, the rewrite-shed
        rung raises, counters fire — and must NOT crash or wedge."""
        t0 = time.perf_counter()
        db = os.path.join(self.dir, "enospc.db")
        mport = _free_port()
        # skip=2 lets the boot/session batches land; the day's traffic
        # hits a disk that is full FOREVER (count -1)
        proc = self._spawn(db, faults_spec="disk.enospc:err:-1:0:2",
                           metrics_port=mport)
        assert await self._wait_ready_or_death(proc)
        pub = MQTTClient(client_id="eno-pub")
        await pub.connect("127.0.0.1", self.port)
        # paced DISTINCT-key retained QoS1 publishes drive commits (a
        # publish with no subscriber and no retain never touches
        # storage; same-key writes coalesce into ONE journal op, which
        # under `batched` would mean one commit for the whole storm);
        # publish until the full disk is counted and the rung is up
        m: dict[str, float] = {}
        acked = 0
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            await pub.publish(f"eno/q{acked}", f"e-{acked}".encode(),
                              qos=1, retain=True, timeout=5.0)
            acked += 1
            if acked % 4 == 0:
                m = _scrape(mport)
                if m.get("maxmq_storage_enospc_failures_total", 0) >= 1 \
                        and m.get("maxmq_storage_disk_full", 0) == 1:
                    break
            await asyncio.sleep(0.05)
        # acks must KEEP flowing while every commit is refused — this
        # is the availability-over-durability half of the rung
        for i in range(10):
            await pub.publish(f"eno/p{i}", f"p-{i}".encode(), qos=1,
                              retain=True, timeout=5.0)
            acked += 1
        # with disk_full up, QoS0 retained rewrites are the first rung
        # off the ladder: shed unconditionally, counted twice over
        for i in range(8):
            await pub.publish("eno/ret", f"r-{i}".encode(), qos=0,
                              retain=True)
        await pub.ping()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            m = _scrape(mport)
            if m.get("maxmq_storage_journal_sheds_total", 0) >= 1:
                break
            await asyncio.sleep(0.2)
        alive = proc.poll() is None
        await pub.close()
        self._kill(proc)
        self.sheet["enospc"] = {
            "alive": alive,
            "acked_during_fault": acked,
            "enospc_failures": m.get(
                "maxmq_storage_enospc_failures_total", 0),
            "breaker_state": m.get("maxmq_storage_breaker_state", -1),
            "disk_full": m.get("maxmq_storage_disk_full", 0),
            "journal_sheds": m.get(
                "maxmq_storage_journal_sheds_total", 0),
            "disk_full_sheds": m.get(
                "maxmq_broker_overload_disk_full_sheds_total", 0),
            "barriers_released_degraded": m.get(
                "maxmq_storage_barriers_released_degraded_total", 0),
        }
        self.sheet["phases"].append(
            {"name": "enospc",
             "dur_s": round(time.perf_counter() - t0, 3)})

    async def _phase_fsync(self) -> None:
        """fsyncgate: two flush failures poison the backend; the
        broker must trip, REOPEN the connection on reprobe, replay the
        parked journal, and recover to a closed breaker — serving the
        whole time."""
        t0 = time.perf_counter()
        db = os.path.join(self.dir, "fsync.db")
        mport = _free_port()
        # two flush failures after the boot batches (skip=2); retained
        # QoS1 traffic keeps commits coming so the half-open reprobe
        # always has a batch to carry
        proc = self._spawn(db, faults_spec="disk.fsync:err:2:0:2",
                           metrics_port=mport, backoff_s=0.2)
        assert await self._wait_ready_or_death(proc)
        pub = MQTTClient(client_id="fs-pub")
        await pub.connect("127.0.0.1", self.port)
        m: dict[str, float] = {}
        i = 0
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            await pub.publish(f"fs/q{i}", f"f-{i}".encode(), qos=1,
                              retain=True, timeout=5.0)
            i += 1
            m = _scrape(mport)
            if m.get("maxmq_storage_breaker_recoveries_total", 0) >= 1 \
                    and m.get("maxmq_storage_breaker_state", 1) == 0:
                break
            await asyncio.sleep(0.1)
        alive = proc.poll() is None
        await pub.close()
        self._kill(proc)
        self.sheet["fsync"] = {
            "alive": alive,
            "acked_during_fault": i,
            "fsync_failures": m.get(
                "maxmq_storage_fsync_failures_total", 0),
            "backend_reopens": m.get(
                "maxmq_storage_backend_reopens_total", 0),
            "breaker_recoveries": m.get(
                "maxmq_storage_breaker_recoveries_total", 0),
            "breaker_state": m.get("maxmq_storage_breaker_state", -1),
        }
        self.sheet["phases"].append(
            {"name": "fsync",
             "dur_s": round(time.perf_counter() - t0, 3)})

    # ------------------------------------------------------------------
    # scoring
    # ------------------------------------------------------------------

    def _score(self) -> None:
        s = self.sheet
        violations: list[str] = []

        def check(ok: bool, what: str) -> None:
            if not ok:
                violations.append(what)

        got_set = set(self.got)
        lost_q1 = set(self.acked_q1) - got_set
        lost_q2 = set(self.acked_q2) - got_set
        lost = lost_q1 | lost_q2
        s["acked_total"] = len(self.acked_q1) + len(self.acked_q2)
        s["delivered_total"] = sum(self.got.values())
        s["pubacked_loss"] = len(lost)
        if self.policy == "always":
            check(not lost,
                  f"always lost {len(lost)} PUBACKed msgs, e.g. "
                  f"{sorted(lost)[:3]}")
        elif self.policy == "batched":
            # per-crash bound: one full op window (batch_ops) plus the
            # traffic the publisher offered inside ~3 commit windows
            # (in-progress + accumulating + slack), plus a constant
            # for session/boot writes sharing the journal
            by_cycle: dict[int, int] = {}
            for ledger in (self.acked_q1, self.acked_q2):
                for payload, cycle in ledger.items():
                    if payload in lost:
                        by_cycle[cycle] = by_cycle.get(cycle, 0) + 1
            bounds = {}
            for cycle, n in sorted(by_cycle.items()):
                rate = self.cycle_rate.get(cycle, 0.0)
                bound = (self.batch_ops
                         + rate * 3.0 * self.batch_ms / 1000.0 + 4)
                bounds[cycle] = round(bound, 1)
                check(n <= bound,
                      f"batched cycle {cycle} lost {n} acked msgs, "
                      f"window bound {bound:.0f}")
                # group commit is FIFO: what survives a crash must be a
                # PREFIX of the cycle's ack sequence, so the lost set
                # must be a contiguous SUFFIX — loss with a survivor
                # after it means the journal reordered a durability
                # promise, a real bug no size window excuses
                order = self.acked_order.get(cycle, [])
                first = next((j for j, p in enumerate(order)
                              if p in lost), len(order))
                holes = [p for p in order[first:] if p not in lost]
                check(not holes,
                      f"batched cycle {cycle} loss is not a FIFO "
                      f"suffix: {holes[:3]} survived after a loss")
            s["batched_loss_by_cycle"] = by_cycle
            s["batched_loss_bounds"] = bounds
        dup_q2 = {p: n for p, n in self.got.items()
                  if n > 1 and p.split(b"-")[1:2] == [b"q2"]}
        s["qos2_duplicates"] = sum(n - 1 for n in dup_q2.values())
        check(s["qos2_duplicates"] == 0,
              f"QoS2 delivered duplicates: {sorted(dup_q2)[:3]}")
        check(s.get("session_losses", 0) == 0,
              f"subscriber session lost {s.get('session_losses')}x")
        if "recovery_p99_ms" in s:
            check(s["recovery_p99_ms"] <= self.slo_recovery_ms,
                  f"recovery p99 {s['recovery_p99_ms']:.0f}ms over "
                  f"SLO {self.slo_recovery_ms:.0f}ms")
        torn = s.get("torn", {})
        if torn:
            check(torn["boot_serving"], "torn-tail boot never served")
            check(torn["quarantined"] == torn["planted"]
                  and torn["quarantine_rows"] == torn["planted"],
                  f"quarantine not exact: planted {torn['planted']}, "
                  f"quarantined {torn['quarantined']}, rows "
                  f"{torn['quarantine_rows']}")
        eno = s.get("enospc", {})
        if eno:
            check(eno["alive"], "broker died under ENOSPC")
            check(eno["enospc_failures"] >= 1, "no ENOSPC counted")
            check(eno["breaker_state"] >= 1,
                  "breaker never opened under ENOSPC")
            check(eno["disk_full"] == 1, "disk_full gauge never rose")
            check(eno["journal_sheds"] >= 1,
                  "ENOSPC rung shed no rewrites")
            check(eno["acked_during_fault"] >= 10,
                  "acks stopped flowing under ENOSPC")
        fs = s.get("fsync", {})
        if fs:
            check(fs["alive"], "broker died under fsync failure")
            check(fs["fsync_failures"] >= 1, "no fsync failure counted")
            check(fs["backend_reopens"] >= 1,
                  "poisoned backend never reopened")
            check(fs["breaker_recoveries"] >= 1,
                  "breaker never recovered after fsync failures")
        s["violations"] = violations
        # the list's length, for a reader that compares numbers
        s["violation_count"] = len(violations)
        s["pass"] = not violations

    # ------------------------------------------------------------------

    async def run(self) -> dict:
        t0 = time.perf_counter()
        try:
            await self._phase_kill_cycles()
            await self._phase_torn_tail()
            await self._phase_enospc()
            await self._phase_fsync()
            self._score()
        finally:
            for proc in self._procs:
                if proc.poll() is None:
                    proc.send_signal(signal.SIGKILL)
            for proc in self._procs:
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    pass
            if self._own_dir:
                shutil.rmtree(self.dir, ignore_errors=True)
        self.sheet["dur_s"] = round(time.perf_counter() - t0, 3)
        return self.sheet


def main(argv: list[str] | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="ADR-024 crash day")
    ap.add_argument("--policy", default="always",
                    choices=("always", "batched", "off"))
    ap.add_argument("--kills", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="3 kill points, tmpfs store, <60s")
    ap.add_argument("--seed", type=int, default=20240)
    args = ap.parse_args(argv)
    day = CrashDay(policy=args.policy, kills=args.kills,
                   smoke=args.smoke, seed=args.seed)
    sheet = asyncio.run(day.run())
    print(json.dumps(sheet, indent=2, default=str))
    return 0 if sheet["pass"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
