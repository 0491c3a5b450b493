"""ADR 020: the "macroday" composed-fault scenario scheduler.

Replays a compressed production day on a live 3-node mesh (A, B, C)
with ``cluster_fwd_durability=chained`` — every phase armed through
the ``faults`` registry so the run is deterministic and replayable:

1. ``connect_storm``   — a concurrent fleet boot against all nodes
2. ``fanin_fanout``    — QoS1 telemetry fan-in (all nodes -> one
                         collector) + command fan-out (one -> many)
3. ``slow_consumer``   — a wedged writer drives the ADR-012 shed
                         ladder up and back down (hysteresis timed)
4. ``sub_churn``       — background subscribe/unsubscribe churn that
                         keeps running through the partition phase
5. ``partition_heal``  — the direct A<->C edge is dropped while churn
                         and a fresh shed are active: the tracked A->C
                         QoS1 stream relays via B under the hop-chained
                         barrier, then the edge heals and convergence
                         is timed
6. ``node_kill``       — B dies with a will-carrying client and a
                         parked QoS1 session window attached: the
                         survivors fire the transferred will exactly
                         once and the session takeover at C redelivers
                         every PUBACKed message

The run is scored against ONE machine-checkable SLO sheet (see
docs/adr/020-macroday-harness.md for the schema): PUBACKed-loss must
be 0 across the kill AND the partition, the will fires exactly once,
recovery/convergence times are recorded, and the per-stage p99 tails
ride along from the ADR-015 tracer.

Since ADR 021 the same day can replay against a SHARDED BOX:
``MacroDay(workers=N)`` boots the three mesh roles as in-box pool
workers over unix-domain bridge links (the local link flavor —
skew≈0, budget-exempt) instead of a TCP mesh, and the ``node_kill``
phase runs as ``worker_kill`` against the same scorer. The
``ConnectionSoak`` scenario reuses the phase scheduler for the
ramped connect-flood soak (tests/test_worker_shard.py, slow lane).

What this harness deliberately does NOT compose is listed in the ADR
(device faults, storage-commit faults, WS listeners, >3 nodes).
"""

from __future__ import annotations

import asyncio
import os
import resource
import shutil
import tempfile
import time

from maxmq_tpu import faults
from maxmq_tpu.broker import (Broker, BrokerOptions, Capabilities,
                              TCPListener, UnixListener)
from maxmq_tpu.broker.workers import worker_sock
from maxmq_tpu.cluster import ClusterManager, PeerSpec
from maxmq_tpu.hooks import AllowHook
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.protocol.packets import Will

MESH = {"A": ["B", "C"], "B": ["A", "C"], "C": ["A", "B"]}
PAYLOAD = b"m" * 96
NOISE = b"n" * 512


class Scenario:
    """The ADR-020 phase scheduler, scenario-agnostic: deterministic
    fault arming with per-phase fired-site accounting, PUBACKed
    stream ledgers (sent <= got is the zero-loss SLO), and ONE
    machine-checkable sheet. MacroDay scripts the production day on
    top of it; ConnectionSoak (ADR 021) scripts the sharded-box
    connect flood."""

    def __init__(self) -> None:
        self.brokers: dict[str, Broker] = {}
        self.sheet: dict = {"phases": []}
        # stream -> (sent payload set, got payload set): every payload
        # in a sent set was PUBACKed to its publisher, so the zero-loss
        # SLO is sent <= got at the end of the run, per stream
        self.streams: dict[str, tuple[set, set]] = {}
        self._armed_now: list[str] = []
        self._clients: list[MQTTClient] = []

    def _arm(self, site: str, mode: str, count: int,
             delay_s: float = 0.05) -> None:
        self._armed_now.append(site)
        faults.arm(site, mode, count, delay_s)

    def _partition(self, a: str, b: str, mode: str = "drop") -> None:
        for src, dst in ((a, b), (b, a)):
            self._armed_now.append(
                f"{faults.CLUSTER_PARTITION}#"
                f"{faults.partition_key(src, dst)}")
        faults.partition(a, b, mode=mode)

    async def _phase(self, name: str, fn) -> dict:
        fired0 = dict(faults.REGISTRY.fired)
        self._armed_now = []
        t0 = time.perf_counter()
        detail = await fn() or {}
        rec = {"name": name,
               "dur_s": round(time.perf_counter() - t0, 3),
               "armed_sites": sorted(set(self._armed_now)),
               "fired": {k: v - fired0.get(k, 0)
                         for k, v in faults.REGISTRY.fired.items()
                         if v != fired0.get(k, 0)}}
        rec.update(detail)
        self.sheet["phases"].append(rec)
        return rec

    async def _poll(self, cond, timeout_s: float) -> float:
        """Seconds until ``cond()`` holds, or -1.0 on timeout."""
        t0 = time.perf_counter()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if cond():
                return time.perf_counter() - t0
            await asyncio.sleep(0.02)
        return -1.0

    async def _connect(self, node: str, cid: str,
                       **kw) -> MQTTClient:
        c = MQTTClient(client_id=cid, **kw)
        await c.connect("127.0.0.1", self.brokers[node].test_port)
        self._clients.append(c)
        return c

    def _stream(self, name: str) -> tuple[set, set]:
        return self.streams.setdefault(name, (set(), set()))

    async def _drain_into(self, client: MQTTClient, got: set,
                          idle: float = 0.8) -> None:
        while True:
            try:
                got.add(bytes((await client.next_message(
                    timeout=idle)).payload))
            except asyncio.TimeoutError:
                return

    async def _settle(self, client: MQTTClient, name: str,
                      timeout_s: float) -> float:
        """Drain ``client`` until the stream's sent set is covered;
        seconds it took, or -1.0 if the deadline passed first."""
        sent, got = self._stream(name)
        t0 = time.perf_counter()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline and not sent <= got:
            await self._drain_into(client, got)
        return (time.perf_counter() - t0) if sent <= got else -1.0

    async def _close_clients(self) -> None:
        for c in self._clients:
            try:
                await c.close()
            except Exception:
                pass


class MacroDay(Scenario):
    """One scripted production day; ``await MacroDay(...).run()``
    returns the SLO sheet dict (``sheet["pass"]`` + violations).

    ``workers=N`` replays the SAME day against a sharded box: the
    three mesh roles become in-box pool workers linked over
    unix-domain bridges (ADR 021), extra workers beyond three join
    the mesh as plain members, and the kill phase scores as
    ``worker_kill``. N below 3 is clamped to 3 — the day's script
    needs its three roles."""

    def __init__(self, *, storm_clients: int = 24,
                 telemetry_msgs: int = 30, command_msgs: int = 20,
                 cut_msgs: int = 20, parked_msgs: int = 30,
                 keepalive: float = 1.0,
                 sync_timeout_ms: int = 1000,
                 # the rank stagger only suppresses the second judge
                 # when the grace exceeds the judges' death-detection
                 # skew (~one keepalive of jitter): keep grace >= 2x
                 # keepalive or both judges fire before the rank-0
                 # stand-down broadcast lands
                 will_grace: float = 2.0,
                 require_relay: bool = True,
                 settle_s: float = 20.0,
                 workers: int = 0) -> None:
        super().__init__()
        self.workers = max(3, workers) if workers else 0
        self.storm_clients = storm_clients
        self.telemetry_msgs = telemetry_msgs
        self.command_msgs = command_msgs
        self.cut_msgs = cut_msgs
        self.parked_msgs = parked_msgs
        self.keepalive = keepalive
        self.sync_timeout_ms = sync_timeout_ms
        self.will_grace = will_grace
        self.require_relay = require_relay
        self.settle_s = settle_s
        self.mgrs: dict[str, ClusterManager] = {}
        self._pool_dir: str | None = None
        self.sheet.update({
            "config": "macroday",
            "nodes": self.workers or 3,
            "topology": (f"in-box pool x{self.workers} (unix mesh)"
                         if self.workers else "mesh A-B-C"),
            "fwd_durability": "chained"})
        if self.workers:
            self.sheet["workers"] = self.workers
        self._churn_stop = asyncio.Event()
        self._churn_rounds = 0

    # -- cluster lifecycle ---------------------------------------------

    async def _boot(self) -> None:
        sharded = self.workers > 0
        members = list(MESH)
        if sharded:
            self._pool_dir = tempfile.mkdtemp(prefix="maxmq-md-pool-")
            members += [f"w{i}" for i in range(3, self.workers)]
        slots = {n: i for i, n in enumerate(members)}
        # sharded: every worker peers with every sibling (the pool is
        # one box); classic: the scripted 3-node mesh
        self._peers = {n: ([p for p in members if p != n] if sharded
                           else MESH[n]) for n in members}
        for name in members:
            caps = Capabilities(
                sys_topic_interval=0, trace_sample_n=1,
                client_byte_budget=1 << 20,
                broker_byte_budget=128 * 1024,
                overload_high_water=0.5, overload_low_water=0.1,
                stall_deadline_ms=2500)
            b = Broker(BrokerOptions(capabilities=caps))
            b.add_hook(AllowHook())
            lst = b.add_listener(TCPListener("t", "127.0.0.1:0"))
            if sharded:
                b.add_listener(UnixListener(
                    "peer-bridge",
                    worker_sock(self._pool_dir, slots[name])))
            await b.serve()
            b.test_port = lst._server.sockets[0].getsockname()[1]
            self.brokers[name] = b
        for name in members:
            if sharded:
                specs = [PeerSpec(p, "", 0, path=worker_sock(
                    self._pool_dir, slots[p]))
                    for p in self._peers[name]]
            else:
                specs = [PeerSpec(p, "127.0.0.1",
                                  self.brokers[p].test_port)
                         for p in self._peers[name]]
            mgr = ClusterManager(
                self.brokers[name], name, specs,
                keepalive=self.keepalive, backoff_initial_s=0.1,
                backoff_max_s=0.5,
                session_sync="always",
                session_sync_timeout_ms=self.sync_timeout_ms,
                session_takeover_timeout_ms=self.sync_timeout_ms,
                fwd_durability="chained")
            self.brokers[name].attach_cluster(mgr)
            for link in mgr.links.values():
                if link.local:
                    link.byte_budget = 0    # ADR 021: budget-exempt
            await mgr.start()
            if mgr.sessions is not None:
                mgr.sessions.will_grace = self.will_grace
            self.mgrs[name] = mgr
        up = await self._poll(
            lambda: all(m.links_up == len(self._peers[n])
                        for n, m in self.mgrs.items()), 30.0)
        if up < 0:
            raise RuntimeError("macroday: cluster never converged")

    async def _teardown(self) -> None:
        self._churn_stop.set()
        task = getattr(self, "_churn_task", None)
        if task is not None:
            try:
                await asyncio.wait_for(task, 5.0)
            except Exception:
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)
        await self._close_clients()
        for b in self.brokers.values():
            try:
                await b.close()
            except Exception:
                pass
        if self._pool_dir is not None:
            shutil.rmtree(self._pool_dir, ignore_errors=True)

    # -- phases --------------------------------------------------------

    async def _phase_connect_storm(self) -> dict:
        nodes = list(MESH)
        times: list[float] = []
        failures = 0

        async def one(i: int) -> None:
            nonlocal failures
            c = MQTTClient(client_id=f"md-storm-{i}")
            t0 = time.perf_counter()
            try:
                await c.connect(
                    "127.0.0.1",
                    self.brokers[nodes[i % 3]].test_port,
                    timeout=10.0)
                times.append(time.perf_counter() - t0)
                await c.disconnect()
            except Exception:
                failures += 1
            finally:
                try:
                    await c.close()
                except Exception:
                    pass

        await asyncio.gather(
            *(one(i) for i in range(self.storm_clients)))
        times.sort()
        p99 = times[min(len(times) - 1,
                        int(len(times) * 0.99))] if times else -1.0
        self.sheet["storm_connack_p99_ms"] = round(p99 * 1e3, 2)
        self.sheet["storm_failures"] = failures
        return {"clients": self.storm_clients, "failures": failures}

    async def _phase_fanin_fanout(self) -> dict:
        # fan-in: one collector at C sees every node's telemetry
        self.collector = await self._connect("C", "md-collector")
        await self.collector.subscribe(("fleet/telemetry/#", 1))
        cmd_a = await self._connect("A", "md-cmd-a")
        await cmd_a.subscribe(("fleet/cmd/#", 1))
        cmd_b = await self._connect("B", "md-cmd-b")
        await cmd_b.subscribe(("fleet/cmd/#", 1))
        ok = await self._poll(
            lambda: bool(self.mgrs["A"].routes.nodes_for(
                "fleet/telemetry/A/0"))
            and bool(self.mgrs["C"].routes.nodes_for("fleet/cmd/run")),
            15.0)
        if ok < 0:
            raise RuntimeError("macroday: routes never converged")
        self.pubs = {n: await self._connect(n, f"md-pub-{n}")
                     for n in MESH}
        sent_t, _got_t = self._stream("telemetry")
        for i in range(self.telemetry_msgs):
            for n in MESH:          # interleaved fan-in burst
                payload = f"t-{n}-{i}-".encode() + PAYLOAD
                await self.pubs[n].publish(
                    f"fleet/telemetry/{n}/{i % 8}", payload, qos=1)
                sent_t.add(payload)
        sent_ca, _ = self._stream("cmd@A")
        sent_cb, _ = self._stream("cmd@B")
        for i in range(self.command_msgs):
            payload = f"c-{i}-".encode() + PAYLOAD
            await self.pubs["C"].publish("fleet/cmd/run", payload,
                                         qos=1)
            sent_ca.add(payload)
            sent_cb.add(payload)
        # command fan-out settles now (cmd@B's subscriber dies with B
        # later); telemetry keeps flowing through the fault phases and
        # settles at the end of the day
        s_a = await self._settle(cmd_a, "cmd@A", self.settle_s)
        s_b = await self._settle(cmd_b, "cmd@B", self.settle_s)
        await self._drain_into(self.collector,
                               self._stream("telemetry")[1])
        return {"telemetry_pubacked": len(sent_t),
                "commands_pubacked": self.command_msgs,
                "cmd_settle_s": round(max(s_a, s_b), 3)}

    async def _wedge(self, node: str, cid: str,
                     topic: str) -> MQTTClient:
        """Wedge one consumer's writer (faults registry) and publish
        local QoS0-fan-out noise until the node sheds."""
        slow = await self._connect(node, cid)
        await slow.subscribe((f"{topic}/#", 0))
        self._arm(f"{faults.CLIENT_WRITE}#{cid}", "hang",
                  count=-1, delay_s=30.0)
        pub = self.pubs[node]
        b = self.brokers[node]
        for _ in range(4000):
            if b.overload.shedding:
                break
            await pub.publish(f"{topic}/x", NOISE, qos=1)
        return slow

    async def _phase_slow_consumer(self) -> dict:
        b = self.brokers["A"]
        await self._wedge("A", "md-slow", "fleet/noise")
        entered = b.overload.shedding
        t0 = time.perf_counter()
        rec = await self._poll(
            lambda: b.overload.stalled_disconnects > 0
            and not b.overload.shedding, 15.0)
        self.sheet["shed_entered"] = entered
        self.sheet["shed_recovered"] = rec >= 0
        self.sheet["shed_recover_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1) if rec >= 0 else -1.0
        faults.disarm(f"{faults.CLIENT_WRITE}#md-slow")
        return {"shed_entered": entered, "recovered": rec >= 0,
                "sheds": b.overload.sheds,
                "stalled_disconnects": b.overload.stalled_disconnects}

    async def _churn_loop(self) -> None:
        churners = [await self._connect(n, f"md-churn-{n}")
                    for n in MESH]
        i = 0
        while not self._churn_stop.is_set():
            c = churners[i % 3]
            filt = f"fleet/churn/{i % 5}/#"
            try:
                await c.subscribe((filt, 1))
                await c.unsubscribe(filt)
            except Exception:
                return          # a dying node's churner just stops
            self._churn_rounds += 1
            i += 1
            await asyncio.sleep(0.03)

    async def _phase_sub_churn(self) -> dict:
        self._churn_task = asyncio.ensure_future(self._churn_loop())
        ok = await self._poll(lambda: self._churn_rounds >= 3, 10.0)
        return {"started": ok >= 0}

    async def _phase_partition_heal(self) -> dict:
        # a fresh shed is active while the edge is cut: composed
        # shed x partition x churn is the point of the macro-scenario
        await self._wedge("A", "md-slow2", "fleet/noise2")
        # any member outside the cut edge can carry the relay (B in
        # the classic mesh; B or an extra worker on a sharded box)
        relays = [n for n in self.mgrs if n not in ("A", "C")]
        relay0 = {n: self.mgrs[n].relay_chain_waits for n in relays}
        self._partition("A", "C")
        down = await self._poll(
            lambda: not self.mgrs["A"].links["C"].connected, 20.0)
        if down < 0:
            raise RuntimeError("macroday: partition never detected")
        sent_t, _got = self._stream("telemetry")
        t0 = time.perf_counter()
        for i in range(self.cut_msgs):
            # A -> C with the direct edge dark: relays via B under the
            # hop-chained barrier (PUBACK still bounded)
            payload = f"cut-{i}-".encode() + PAYLOAD
            await self.pubs["A"].publish(f"fleet/telemetry/A/{i % 8}",
                                         payload, qos=1)
            sent_t.add(payload)
        puback_s = round(time.perf_counter() - t0, 3)
        faults.heal("A", "C")
        t_heal = time.perf_counter()
        up = await self._poll(
            lambda: all(m.links_up == len(self._peers[n])
                        for n, m in self.mgrs.items()), 30.0)
        settle = await self._settle(self.collector, "telemetry",
                                    self.settle_s)
        self.sheet["heal_convergence_ms"] = round(
            (time.perf_counter() - t_heal) * 1e3, 1) \
            if up >= 0 and settle >= 0 else -1.0
        self.sheet["relay_chain_waits"] = sum(
            self.mgrs[n].relay_chain_waits - relay0[n]
            for n in relays)
        faults.disarm(f"{faults.CLIENT_WRITE}#md-slow2")
        rec = await self._poll(
            lambda: not self.brokers["A"].overload.shedding, 15.0)
        a = self.mgrs["A"]
        return {"cut_pubacked": self.cut_msgs,
                "cut_puback_s": puback_s,
                "shed_during_cut": self.brokers["A"].overload.sheds
                >= 2,
                "shed_recovered_after": rec >= 0,
                "fwd_barrier_waits": a.fwd_barrier_waits,
                "fwd_barrier_timeouts": a.fwd_barrier_timeouts,
                "fwd_barrier_degraded": a.fwd_barrier_degraded,
                "relay_chain_waits_b":
                    self.mgrs["B"].relay_chain_waits - relay0["B"],
                "relay_chain_timeouts_b":
                    self.mgrs["B"].relay_chain_timeouts}

    async def _phase_node_kill(self) -> dict:
        will_sub = await self._connect("A", "md-will-sub")
        await will_sub.subscribe(("fleet/will/#", 1))
        wc = MQTTClient(client_id="md-will", version=5,
                        clean_start=False, session_expiry=600,
                        will=Will(topic="fleet/will/b", payload=b"rip",
                                  qos=1))
        await wc.connect("127.0.0.1", self.brokers["B"].test_port)
        sess = MQTTClient(client_id="md-sess", version=5,
                          clean_start=False, session_expiry=3600)
        await sess.connect("127.0.0.1", self.brokers["B"].test_port)
        await sess.subscribe(("fleet/park/#", 1))
        ok = await self._poll(
            lambda: all("md-sess" in self.mgrs[n].sessions.ledger
                        and "md-will" in self.mgrs[n].sessions.ledger
                        and self.mgrs[n].sessions.ledger[
                            "md-will"].will
                        for n in ("A", "C")), 15.0)
        if ok < 0:
            raise RuntimeError("macroday: session/will never "
                               "replicated off B")
        await sess.disconnect()     # the parked window fills next
        pub_b = await self._connect("B", "md-pub-park")
        sent_k, got_k = self._stream("parked")
        for i in range(self.parked_msgs):
            # PUBACKed AT the owner: the ack carried the journal +
            # replication barrier, so these must survive B's death
            payload = f"p-{i}-".encode() + PAYLOAD
            await pub_b.publish("fleet/park/m", payload, qos=1)
            sent_k.add(payload)
        await self.brokers["B"].close()         # the node "dies"
        await self._poll(
            lambda: not self.mgrs["A"].links["B"].connected
            and not self.mgrs["C"].links["B"].connected, 20.0)
        t0 = time.perf_counter()
        sess_c = MQTTClient(client_id="md-sess", version=5,
                            clean_start=False, session_expiry=3600)
        await sess_c.connect("127.0.0.1",
                             self.brokers["C"].test_port)
        self._clients.append(sess_c)
        self.sheet["takeover_recovery_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)
        self.sheet["takeover_session_present"] = bool(
            sess_c.session_present)
        await self._drain_into(sess_c, got_k, idle=1.2)
        wills = await self._poll(
            lambda: (self.mgrs["A"].sessions.wills_fired
                     + self.mgrs["C"].sessions.wills_fired) >= 1,
            15.0)
        await asyncio.sleep(self.will_grace * 2)    # late 2nd fire?
        fired = (self.mgrs["A"].sessions.wills_fired
                 + self.mgrs["C"].sessions.wills_fired)
        delivered = []
        while True:
            try:
                delivered.append((await will_sub.next_message(
                    timeout=1.0)).payload)
            except asyncio.TimeoutError:
                break
        self.sheet["wills_fired"] = fired
        self.sheet["wills_delivered"] = delivered.count(b"rip")
        self.sheet["will_detect_s"] = round(wills, 3) \
            if wills >= 0 else -1.0
        sC = self.mgrs["C"].sessions
        return {"parked_pubacked": len(sent_k),
                "takeovers": sC.takeovers,
                "takeovers_degraded": sC.takeovers_degraded,
                "wills_fired": fired}

    # -- scoring -------------------------------------------------------

    @staticmethod
    def _trace_stanza(tracer) -> dict:
        """The ADR-015 stanza of the sheet: what was sampled, and the
        per-stage and per-QoS quantiles."""
        d = {"sampled": tracer.sampled,
             "slow_captured": tracer.slow_captured,
             "stages": tracer.stage_quantiles(),
             "e2e": tracer.e2e_quantiles()}
        cross = tracer.cross_quantiles()
        if cross or tracer.remote_attached:
            d["cross_node"] = cross
            d["remote_reports"] = tracer.remote_attached
            d["remote_orphans"] = tracer.remote_orphans
        return d

    def _score(self) -> None:
        violations: list[str] = []

        def check(cond: bool, what: str) -> None:
            if not cond:
                violations.append(what)

        loss = {name: len(sent - got)
                for name, (sent, got) in self.streams.items()}
        self.sheet["pubacked_loss_per_stream"] = loss
        self.sheet["pubacked_loss"] = sum(loss.values())
        self.sheet["pubacked_total"] = sum(
            len(sent) for sent, _ in self.streams.values())
        check(self.sheet["pubacked_loss"] == 0,
              f"PUBACKed-loss must be 0, got {loss}")
        check(self.sheet.get("storm_failures") == 0,
              "connect storm saw refused/failed connects")
        check(self.sheet.get("wills_fired") == 1,
              f"will must fire exactly once, fired "
              f"{self.sheet.get('wills_fired')}")
        check(self.sheet.get("wills_delivered") == 1,
              f"will must be delivered exactly once, saw "
              f"{self.sheet.get('wills_delivered')}")
        check(bool(self.sheet.get("takeover_session_present")),
              "takeover at C lost the session")
        check(self.sheet.get("takeover_recovery_ms", -1) >= 0,
              "takeover recovery time not recorded")
        check(self.sheet.get("heal_convergence_ms", -1) >= 0,
              "partition heal never converged")
        check(bool(self.sheet.get("shed_entered")),
              "slow consumer never drove the shed ladder")
        check(bool(self.sheet.get("shed_recovered")),
              "shed never recovered (hysteresis broken)")
        if self.require_relay:
            check(self.sheet.get("relay_chain_waits", 0) >= 1,
                  "cut-edge stream never exercised the hop-chained "
                  "relay barrier")
        check(self._churn_rounds >= 3, "subscription churn never ran")
        self.sheet["churn_rounds"] = self._churn_rounds
        self.sheet["blips_detected"] = sum(
            m.blips_detected for m in self.mgrs.values())
        self.sheet["blip_resyncs"] = sum(
            m.blip_resyncs for m in self.mgrs.values())
        tr = self._trace_stanza(self.brokers["A"].tracer)
        self.sheet["trace"] = tr
        self.sheet["stage_p99_ms"] = {
            stage: row.get("p99_ms")
            for stage, row in tr.get("stages", {}).items()}
        self.sheet["violations"] = violations
        self.sheet["pass"] = not violations

    # -- entry point ---------------------------------------------------

    async def run(self) -> dict:
        t0 = time.perf_counter()
        try:
            await self._boot()
            await self._phase("connect_storm",
                              self._phase_connect_storm)
            await self._phase("fanin_fanout",
                              self._phase_fanin_fanout)
            await self._phase("slow_consumer",
                              self._phase_slow_consumer)
            await self._phase("sub_churn", self._phase_sub_churn)
            await self._phase("partition_heal",
                              self._phase_partition_heal)
            self._churn_stop.set()
            # sharded box: B *is* a worker, so the same phase + scorer
            # report the pool's crash story under its own name
            await self._phase(
                "worker_kill" if self.workers else "node_kill",
                self._phase_node_kill)
            # final settle: the collector at C must hold every
            # PUBACKed telemetry payload, including the cut-edge leg
            await self._settle(self.collector, "telemetry",
                               self.settle_s)
            self._score()
        finally:
            self._churn_stop.set()
            await self._teardown()
            faults.clear()
        self.sheet["day_s"] = round(time.perf_counter() - t0, 2)
        return self.sheet


class ConnectionSoak(Scenario):
    """ADR-021 connection soak on the macroday scheduler: ramp a
    connect flood against an in-box worker pool with the ADR-012
    connect-refusal and stall ladders ENGAGED, hold the fleet, then
    stream a tracked QoS1 sample across the worker mesh.

    The SLO is EXPLAINABILITY, not a perfect score: a refused connect
    is fine iff an overload counter accounts for it, and a wedged
    consumer's disconnect is fine iff the stall ladder fired — zero
    UNEXPLAINED connect failures, zero unexplained PUBACKed loss.

    Targets ``connections`` (default 100K) where the fd budget
    allows; the fleet is clamped to RLIMIT_NOFILE (each held
    connection costs ~4 fds with the clients in-process) so the soak
    runs truthfully on small boxes. ``MAXMQ_SOAK_CONNECTIONS`` pins
    the target explicitly."""

    def __init__(self, *, workers: int = 2,
                 connections: int | None = None,
                 ramp_batch: int = 256, hold_s: float = 5.0,
                 tracked_msgs: int = 40,
                 settle_s: float = 20.0) -> None:
        super().__init__()
        self.workers = workers
        env = os.environ.get("MAXMQ_SOAK_CONNECTIONS")
        target = int(env) if env else (connections or 100_000)
        soft = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
        self.connections = max(64, min(target, (soft - 512) // 4))
        self.ramp_batch = ramp_batch
        self.hold_s = hold_s
        self.tracked_msgs = tracked_msgs
        self.settle_s = settle_s
        self.sheet.update({"config": "soak", "workers": workers,
                           "target_connections": target,
                           "fleet": self.connections})
        self._fleet: list[MQTTClient] = []
        self._pool: list[Broker] = []
        self._ports: list[int] = []

    def _refusals(self) -> int:
        return sum(b.overload.connects_refused
                   + b.overload.half_open_refused for b in self._pool)

    # -- phases --------------------------------------------------------

    async def _phase_ramp(self) -> dict:
        """Batched connect flood, round-robin across the workers. The
        token bucket (connect_rate) and half-open cap WILL refuse
        spikes — each refusal is retried, and the broker-side refusal
        counters must explain every client-side failure."""
        failures = 0

        async def one(i: int) -> None:
            nonlocal failures
            port = self._ports[i % len(self._ports)]
            for attempt in range(40):
                c = MQTTClient(client_id=f"soak-{i}", keepalive=600)
                try:
                    await c.connect("127.0.0.1", port, timeout=10.0)
                    self._fleet.append(c)
                    return
                except Exception:
                    failures += 1
                    try:
                        await c.close()
                    except Exception:
                        pass
                    await asyncio.sleep(0.05 * min(attempt + 1, 8))

        t0 = time.perf_counter()
        for base in range(0, self.connections, self.ramp_batch):
            batch = range(base, min(base + self.ramp_batch,
                                    self.connections))
            await asyncio.gather(*(one(i) for i in batch))
        ramp_s = time.perf_counter() - t0
        refused = self._refusals()
        self.sheet["connected"] = len(self._fleet)
        self.sheet["connect_failures"] = failures
        self.sheet["connect_refused"] = refused
        self.sheet["unexplained_connect_failures"] = max(
            0, failures - refused)
        self.sheet["ramp_connects_per_sec"] = round(
            len(self._fleet) / ramp_s, 1) if ramp_s > 0 else -1.0
        return {"connected": len(self._fleet), "refused": refused,
                "failures": failures}

    async def _phase_hold(self) -> dict:
        """Hold the fleet; a ping sample proves the box still serves
        under the standing-connection load, and nobody held may drop."""
        await asyncio.sleep(self.hold_s)
        step = max(1, len(self._fleet) // 64)
        sample, ok = self._fleet[::step], 0
        for c in sample:
            try:
                await c.ping(timeout=5.0)
                ok += 1
            except Exception:
                pass
        dropped = sum(1 for c in self._fleet
                      if c.writer is None or c.writer.is_closing())
        self.sheet["hold_dropped"] = dropped
        self.sheet["held"] = len(self._fleet) - dropped
        return {"sample": len(sample), "sample_pings_ok": ok,
                "dropped": dropped}

    async def _phase_stall(self) -> dict:
        """One wedged consumer under QoS1 noise drives the ADR-012
        shed ladder into a stall disconnect — the EXPLAINED way to
        lose a client mid-soak."""
        b = self._pool[0]
        slow = MQTTClient(client_id="soak-slow")
        await slow.connect("127.0.0.1", self._ports[0])
        self._clients.append(slow)
        await slow.subscribe(("soak/noise/#", 0))
        self._arm(f"{faults.CLIENT_WRITE}#soak-slow", "hang",
                  count=-1, delay_s=30.0)
        pub = MQTTClient(client_id="soak-noise")
        await pub.connect("127.0.0.1", self._ports[0])
        self._clients.append(pub)
        for _ in range(4000):
            if b.overload.shedding:
                break
            await pub.publish("soak/noise/x", NOISE, qos=1)
        stalled = await self._poll(
            lambda: b.overload.stalled_disconnects > 0, 15.0)
        faults.disarm(f"{faults.CLIENT_WRITE}#soak-slow")
        rec = await self._poll(lambda: not b.overload.shedding, 15.0)
        self.sheet["stall_engaged"] = stalled >= 0
        self.sheet["stalled_disconnects"] = \
            b.overload.stalled_disconnects
        return {"engaged": stalled >= 0, "recovered": rec >= 0,
                "sheds": b.overload.sheds}

    async def _phase_tracked(self) -> dict:
        """A tracked QoS1 stream crossing the worker mesh while the
        fleet is still attached: sent <= got or the soak fails."""
        sent, got = self._stream("tracked")
        sub = MQTTClient(client_id="soak-track-sub")
        await sub.connect("127.0.0.1", self._ports[0])
        self._clients.append(sub)
        await sub.subscribe(("soak/track", 1))
        pub = MQTTClient(client_id="soak-track-pub")
        await pub.connect("127.0.0.1", self._ports[-1])
        self._clients.append(pub)
        ok = await self._poll(
            lambda: bool(self._pool[-1].cluster.routes.nodes_for(
                "soak/track")) or len(self._ports) == 1, 15.0)
        if ok < 0:
            raise RuntimeError("soak: tracked route never converged")
        for i in range(self.tracked_msgs):
            payload = f"trk-{i}-".encode() + PAYLOAD
            await pub.publish("soak/track", payload, qos=1)
            sent.add(payload)
        settle = await self._settle(sub, "tracked", self.settle_s)
        self.sheet["tracked_pubacked"] = len(sent)
        self.sheet["unexplained_loss"] = len(sent - got)
        return {"pubacked": len(sent), "settle_s": round(settle, 3),
                "loss": len(sent - got)}

    # -- scoring / entry point -----------------------------------------

    def _score(self) -> None:
        violations: list[str] = []

        def check(cond: bool, what: str) -> None:
            if not cond:
                violations.append(what)

        check(self.sheet.get("connected", 0) >= self.connections,
              f"fleet never fully connected "
              f"({self.sheet.get('connected')}/{self.connections})")
        check(self.sheet.get("connect_refused", 0) >= 1,
              "connect-refusal ladder never engaged")
        check(self.sheet.get("unexplained_connect_failures", 1) == 0,
              "connect failures the refusal counters cannot explain")
        check(self.sheet.get("hold_dropped", 1) == 0,
              "held connections dropped mid-soak")
        check(bool(self.sheet.get("stall_engaged")),
              "stall ladder never engaged")
        check(self.sheet.get("unexplained_loss", 1) == 0,
              "tracked QoS1 stream lost PUBACKed payloads")
        self.sheet["violations"] = violations
        self.sheet["pass"] = not violations

    async def run(self) -> dict:
        from maxmq_tpu.broker.workers import inprocess_pool
        from maxmq_tpu.utils.config import Config

        conf = Config(
            connect_rate=800.0, connect_burst=64,
            connect_half_open_max=512,
            broker_client_byte_budget=1 << 20,
            broker_byte_budget=128 * 1024,
            broker_overload_high_water=0.5,
            broker_overload_low_water=0.1,
            stall_deadline_ms=2500)
        link_dir = tempfile.mkdtemp(prefix="maxmq-soak-")
        t0 = time.perf_counter()
        try:
            async with inprocess_pool(self.workers, link_dir=link_dir,
                                      conf=conf) as (brokers, ports):
                self._pool, self._ports = brokers, ports
                await self._phase("connect_ramp", self._phase_ramp)
                await self._phase("hold", self._phase_hold)
                await self._phase("stall_ladder", self._phase_stall)
                await self._phase("tracked_stream",
                                  self._phase_tracked)
                self._score()
                await self._close_clients()
                for c in self._fleet:
                    try:
                        await c.close()
                    except Exception:
                        pass
        finally:
            faults.clear()
            shutil.rmtree(link_dir, ignore_errors=True)
        self.sheet["soak_s"] = round(time.perf_counter() - t0, 2)
        return self.sheet
