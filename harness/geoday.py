"""ADR 022: the "geoday" WAN-shaped geo-federation macro-scenario.

Three single-node "regions" — ``eu``, ``us``, ``ap`` — form a full
mesh whose links are SHAPED through the ``cluster.shape`` fault
family at real WAN round trips (eu<->us 30ms, us<->ap 80ms,
eu<->ap 150ms by default, scaled by ``rtt_scale``) with asymmetric
bandwidth on the ``ap`` legs and a small probabilistic loss on the
eu->us data path. Every cluster rail the earlier days proved at
loopback RTT replays here under latency the deadlines must absorb:

1. ``shape_links``        — arm the shapes, let the ADR-017 clock
                            probes LEARN each link's RTT (the
                            RTT-adaptive deadlines feed off the
                            measured EWMA, not the configured value),
                            baseline the flap counters
2. ``regional_fanin``     — per-region QoS1 publishers feed a global
                            aggregator in ``us`` across the shaped
                            mesh (the lossy eu->us leg exercises the
                            ADR-020 blip audit as REAL loss recovery)
3. ``cross_region_share`` — a ``$share`` worker group spanning all
                            three regions consumes a QoS1 job stream
                            exactly once
4. ``region_outage_heal`` — the ``ap`` region dies wholesale with a
                            will-carrying client and a durable QoS1
                            session attached; load keeps flowing
                            (PUBACKed + parked against the dead
                            link), the stranded client re-attaches at
                            a SURVIVOR — the epoch-fenced takeover
                            plus the ADR-022 parked-forward rehome
                            closes the ADR-021 dead-owner blackhole —
                            then the region reboots ON ITS OLD
                            ADDRESS and a post-heal stream must
                            converge within an RTT-scaled budget
5. ``roam_takeover``      — a subscriber roams mid-stream from ``eu``
                            to ``us`` via the epoch-fenced takeover;
                            the replicated inflight window follows it
                            across the shaped mesh

The SLO sheet: zero PUBACKed
loss, the will fires exactly once, ZERO false link flaps on healthy
shaped links, heal-convergence and roam-takeover bounded relative to
the configured RTT.

What the shape model deliberately does NOT emulate is listed in
docs/adr/022-wan-shaping.md (path MTU, TCP congestion control, DNS).
"""

from __future__ import annotations

import asyncio
import time

from maxmq_tpu import faults
from maxmq_tpu.broker import (Broker, BrokerOptions, Capabilities,
                              TCPListener)
from maxmq_tpu.cluster import ClusterManager, PeerSpec
from maxmq_tpu.hooks import AllowHook
from maxmq_tpu.mqtt_client import MQTTClient
from maxmq_tpu.protocol.packets import Will

from .macroday import PAYLOAD, Scenario

REGIONS = ("eu", "us", "ap")
# configured round trips per undirected region pair, milliseconds
RTT_MS = {("eu", "us"): 30.0, ("us", "ap"): 80.0, ("eu", "ap"): 150.0}
# asymmetric bandwidth: the ap region sits behind a thin uplink
RATE_BPS = {("eu", "ap"): 2_000_000, ("ap", "eu"): 500_000,
            ("us", "ap"): 2_000_000, ("ap", "us"): 500_000}


class GeoDay(Scenario):
    """One scripted WAN day; ``await GeoDay(...).run()`` returns the
    SLO sheet dict (``sheet["pass"]`` + violations).

    ``rtt_scale`` compresses every configured RTT (the CI smoke runs
    at 0.1 — 3/8/15ms — to stay under a minute); budgets scale with
    it, so the bounds stay RTT-relative instead of wall-clock
    guesses."""

    def __init__(self, *, rtt_scale: float = 1.0,
                 fanin_msgs: int = 20, share_msgs: int = 18,
                 outage_msgs: int = 20, roam_msgs: int = 12,
                 keepalive: float = 1.0, will_grace: float = 1.0,
                 sync_timeout_ms: int = 1000,
                 rtt_deadline_k: float = 4.0,
                 fanin_loss: float = 0.02,
                 settle_s: float = 25.0) -> None:
        super().__init__()
        self.rtt_scale = rtt_scale
        self.fanin_msgs = fanin_msgs
        self.share_msgs = share_msgs
        self.outage_msgs = outage_msgs
        self.roam_msgs = roam_msgs
        self.keepalive = keepalive
        self.will_grace = will_grace
        self.sync_timeout_ms = sync_timeout_ms
        self.rtt_deadline_k = rtt_deadline_k
        self.fanin_loss = fanin_loss
        self.settle_s = settle_s
        self.mgrs: dict[str, ClusterManager] = {}
        self.max_rtt_ms = max(RTT_MS.values()) * rtt_scale
        self.sheet.update({
            "config": "geoday",
            "nodes": 3,
            "topology": "mesh eu-us-ap (WAN-shaped)",
            "rtt_ms": round(self.max_rtt_ms, 3),
            "rtt_map_ms": {f"{a}-{b}": round(v * rtt_scale, 3)
                           for (a, b), v in RTT_MS.items()},
            "fwd_durability": "chained"})
        self._flap_base: dict[tuple[str, str], int] = {}
        self._ap_flap_allowance = 0

    # -- shaping helpers -----------------------------------------------

    def _pair_rtt_s(self, a: str, b: str) -> float:
        key = (a, b) if (a, b) in RTT_MS else (b, a)
        return RTT_MS[key] * self.rtt_scale / 1e3

    def _shape_pair(self, a: str, b: str, *, loss_ab: float = 0.0)\
            -> None:
        """Arm both directions of one region pair: half the configured
        RTT of one-way delay each way, a touch of jitter, the
        asymmetric rate plan, and (optionally) loss on a->b."""
        rtt_s = self._pair_rtt_s(a, b)
        jitter = rtt_s * 0.02
        for src, dst, loss in ((a, b, loss_ab), (b, a, 0.0)):
            faults.shape(src, dst,
                         delay_ms=rtt_s / 2 * 1e3,
                         jitter_ms=jitter * 1e3,
                         rate_bps=RATE_BPS.get((src, dst), 0),
                         loss=loss)
            self._armed_now.append(
                f"{faults.CLUSTER_SHAPE}#"
                f"{faults.partition_key(src, dst)}")

    def _link_flaps(self) -> dict[tuple[str, str], int]:
        out = {}
        for name, mgr in self.mgrs.items():
            for peer, st in mgr.membership.peers.items():
                out[(name, peer)] = st.flaps
        return out

    # -- cluster lifecycle ---------------------------------------------

    async def _boot(self, reuse_port: dict | None = None) -> None:
        ports = reuse_port or {}
        for name in REGIONS:
            await self._boot_node(name, port=ports.get(name, 0))
        for name in REGIONS:
            await self._boot_manager(name)
        up = await self._poll(
            lambda: all(m.links_up == 2 for m in self.mgrs.values()),
            30.0)
        if up < 0:
            raise RuntimeError("geoday: cluster never converged")

    async def _boot_node(self, name: str, port: int = 0) -> None:
        caps = Capabilities(
            sys_topic_interval=0, trace_sample_n=1,
            client_byte_budget=1 << 20,
            broker_byte_budget=256 * 1024,
            overload_high_water=0.5, overload_low_water=0.1,
            stall_deadline_ms=2500)
        b = Broker(BrokerOptions(capabilities=caps))
        b.add_hook(AllowHook())
        lst = b.add_listener(TCPListener("t", f"127.0.0.1:{port}"))
        await b.serve()
        b.test_port = lst._server.sockets[0].getsockname()[1]
        self.brokers[name] = b

    async def _boot_manager(self, name: str) -> None:
        specs = [PeerSpec(p, "127.0.0.1", self.brokers[p].test_port)
                 for p in REGIONS if p != name]
        mgr = ClusterManager(
            self.brokers[name], name, specs,
            keepalive=self.keepalive, backoff_initial_s=0.1,
            backoff_max_s=0.5,
            session_sync="always",
            session_sync_timeout_ms=self.sync_timeout_ms,
            session_takeover_timeout_ms=self.sync_timeout_ms,
            fwd_durability="chained",
            rtt_deadline_k=self.rtt_deadline_k)
        self.brokers[name].attach_cluster(mgr)
        await mgr.start()
        if mgr.sessions is not None:
            mgr.sessions.will_grace = self.will_grace
        self.mgrs[name] = mgr

    async def _teardown(self) -> None:
        await self._close_clients()
        for b in self.brokers.values():
            try:
                await b.close()
            except Exception:
                pass

    # -- phases --------------------------------------------------------

    async def _phase_shape_links(self) -> dict:
        self._shape_pair("eu", "us", loss_ab=self.fanin_loss)
        self._shape_pair("us", "ap")
        self._shape_pair("eu", "ap")
        # the deadlines derive from the MEASURED EWMA: wait until every
        # region has learned a finite estimate for its slowest link
        # (the probes ride the shaped data path, so learned ~= shaped)
        want = {n: max(self._pair_rtt_s(n, p)
                       for p in REGIONS if p != n)
                for n in REGIONS}
        learned = await self._poll(
            lambda: all(m.max_rtt_s() >= want[n] * 0.5
                        for n, m in self.mgrs.items()),
            60.0)
        self.sheet["rtt_learn_s"] = round(learned, 3)
        self.sheet["rtt_learned_ms"] = {
            n: round(m.max_rtt_s() * 1e3, 2)
            for n, m in self.mgrs.items()}
        self._flap_base = self._link_flaps()
        return {"learned": learned >= 0,
                "rtt_learned_ms": self.sheet["rtt_learned_ms"]}

    async def _phase_regional_fanin(self) -> dict:
        self.aggregator = await self._connect("us", "geo-agg")
        await self.aggregator.subscribe(("geo/telemetry/#", 1))
        ok = await self._poll(
            lambda: all(bool(m.routes.nodes_for("geo/telemetry/x/0"))
                        for n, m in self.mgrs.items() if n != "us"),
            20.0)
        if ok < 0:
            raise RuntimeError("geoday: fan-in routes never converged")
        self.pubs = {n: await self._connect(n, f"geo-pub-{n}")
                     for n in REGIONS}
        sent, _got = self._stream("fanin")
        t0 = time.perf_counter()
        for i in range(self.fanin_msgs):
            for n in REGIONS:
                payload = f"f-{n}-{i}-".encode() + PAYLOAD
                await self.pubs[n].publish(
                    f"geo/telemetry/{n}/{i % 4}", payload, qos=1)
                sent.add(payload)
        puback_s = time.perf_counter() - t0
        settle = await self._settle(self.aggregator, "fanin",
                                    self.settle_s)
        self.sheet["fanin_pubacked"] = len(sent)
        self.sheet["fanin_settle_s"] = round(settle, 3)
        return {"pubacked": len(sent),
                "puback_s": round(puback_s, 3),
                "settle_s": round(settle, 3),
                "blips_detected": sum(m.blips_detected
                                      for m in self.mgrs.values()),
                "shape_drops_in": sum(m.shape_drops_in
                                      for m in self.mgrs.values())}

    async def _phase_cross_region_share(self) -> dict:
        workers = {}
        for n in REGIONS:
            w = await self._connect(n, f"geo-worker-{n}")
            await w.subscribe(("$share/geo/geo/jobs/#", 1))
            workers[n] = w
        ok = await self._poll(
            lambda: all(bool(m.routes.nodes_for("geo/jobs/j"))
                        for m in self.mgrs.values()), 20.0)
        if ok < 0:
            raise RuntimeError("geoday: $share routes never converged")
        sent, got = self._stream("jobs")
        for i in range(self.share_msgs):
            payload = f"j-{i}-".encode() + PAYLOAD
            await self.pubs["eu"].publish(f"geo/jobs/{i % 4}", payload,
                                          qos=1)
            sent.add(payload)
        copies: list[bytes] = []

        async def drain_worker(w) -> None:
            while True:
                try:
                    msg = await w.next_message(timeout=1.0)
                except asyncio.TimeoutError:
                    return
                copies.append(bytes(msg.payload))
                got.add(bytes(msg.payload))

        deadline = time.monotonic() + self.settle_s
        while time.monotonic() < deadline and not sent <= got:
            await asyncio.gather(*(drain_worker(w)
                                   for w in workers.values()))
        dupes = len(copies) - len(set(copies))
        self.sheet["share_pubacked"] = len(sent)
        self.sheet["share_duplicates"] = dupes
        # unsubscribe BEFORE the outage phase: a $share member inside
        # the doomed region must not leave a stale route that parks
        # job copies against a region that never returns
        for w in workers.values():
            await w.unsubscribe("$share/geo/geo/jobs/#")
        return {"pubacked": len(sent), "delivered": len(copies),
                "duplicates": dupes}

    async def _phase_region_outage_heal(self) -> dict:
        # a will-carrying client and a durable session live in ap
        will_sub = await self._connect("eu", "geo-will-sub")
        await will_sub.subscribe(("geo/will/#", 1))
        wc = MQTTClient(client_id="geo-will", version=5,
                        clean_start=False, session_expiry=600,
                        will=Will(topic="geo/will/ap", payload=b"rip",
                                  qos=1))
        await wc.connect("127.0.0.1", self.brokers["ap"].test_port)
        self._clients.append(wc)
        sess = MQTTClient(client_id="geo-sess", version=5,
                          clean_start=False, session_expiry=3600)
        await sess.connect("127.0.0.1", self.brokers["ap"].test_port)
        await sess.subscribe(("geo/park/#", 1))
        ok = await self._poll(
            lambda: all("geo-sess" in self.mgrs[n].sessions.ledger
                        and "geo-will" in self.mgrs[n].sessions.ledger
                        and self.mgrs[n].sessions.ledger[
                            "geo-will"].will
                        for n in ("eu", "us")), 20.0)
        if ok < 0:
            raise RuntimeError("geoday: session/will never left ap")
        await sess.disconnect()
        ap_port = self.brokers["ap"].test_port
        # flaps from here to re-convergence are the OUTAGE, not noise
        pre_kill = self._link_flaps()
        await self.brokers["ap"].close()
        await self._poll(
            lambda: not self.mgrs["eu"].links["ap"].connected
            and not self.mgrs["us"].links["ap"].connected, 30.0)
        # QoS1 load against the dead region: PUBACKed (degraded
        # barrier) + parked on the eu->ap link, pinned to a dead owner
        sent, got = self._stream("outage")
        for i in range(self.outage_msgs):
            payload = f"o-{i}-".encode() + PAYLOAD
            await self.pubs["eu"].publish(f"geo/park/{i % 4}", payload,
                                          qos=1)
            sent.add(payload)
        await self._poll(lambda: self.mgrs["eu"].fwd_parked_now > 0,
                         10.0)
        parked = self.mgrs["eu"].fwd_parked_now
        # the survivors judge the dead region: the will fires once
        wills = await self._poll(
            lambda: (self.mgrs["eu"].sessions.wills_fired
                     + self.mgrs["us"].sessions.wills_fired) >= 1,
            30.0 + self.rtt_deadline_k * self._pair_rtt_s("eu", "ap"))
        # the stranded client gives up on its home region and attaches
        # at the SURVIVOR: the epoch-fenced takeover claims the session
        # off the dead owner, and the claim-driven ADR-022 rehome moves
        # the parked eu->ap copies onto the us link — the ADR-021
        # dead-owner blackhole, closed
        t_rec = time.perf_counter()
        sess_us = MQTTClient(client_id="geo-sess", version=5,
                             clean_start=False, session_expiry=3600)
        await sess_us.connect("127.0.0.1",
                              self.brokers["us"].test_port)
        self._clients.append(sess_us)
        self.sheet["outage_takeover_recovery_ms"] = round(
            (time.perf_counter() - t_rec) * 1e3, 2)
        self.sheet["outage_session_present"] = bool(
            sess_us.session_present)
        settle = await self._settle(sess_us, "outage", self.settle_s
                                    + self.rtt_deadline_k
                                    * self._pair_rtt_s("eu", "ap"))
        rehomed = sum(m.fwd_parked_rehomed for m in self.mgrs.values())
        # the region heals: a fresh broker on the SAME address, and a
        # post-heal stream out of the reborn region must reach the
        # global aggregator to call the heal converged
        t_heal = time.perf_counter()
        await self._boot_node("ap", port=ap_port)
        await self._boot_manager("ap")
        up = await self._poll(
            lambda: all(m.links_up == 2 for m in self.mgrs.values()),
            60.0)
        if up < 0:
            raise RuntimeError("geoday: region heal never converged")
        heal_pub = await self._connect("ap", "geo-postheal")
        sent2, _got2 = self._stream("postheal")
        for i in range(self.outage_msgs // 2):
            payload = f"h-{i}-".encode() + PAYLOAD
            await heal_pub.publish(f"geo/telemetry/heal/{i % 4}",
                                   payload, qos=1)
            sent2.add(payload)
        heal_settle = await self._settle(
            self.aggregator, "postheal", self.settle_s
            + self.rtt_deadline_k * self._pair_rtt_s("eu", "ap"))
        self.sheet["heal_convergence_ms"] = round(
            (time.perf_counter() - t_heal) * 1e3, 1) \
            if heal_settle >= 0 else -1.0
        await asyncio.sleep(self.will_grace * 2)    # a late 2nd fire?
        fired = (self.mgrs["eu"].sessions.wills_fired
                 + self.mgrs["us"].sessions.wills_fired
                 + self.mgrs["ap"].sessions.wills_fired)
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
        # outage flaps on ap links are EXPECTED: remember the budget
        # the false-flap scorer must exclude
        post = self._link_flaps()
        self._ap_flap_allowance = sum(
            post[k] - pre_kill.get(k, 0) for k in post
            if "ap" in k)
        return {"parked_during_outage": parked,
                "outage_pubacked": len(sent),
                "settle_s": round(settle, 3),
                "rehomed": rehomed,
                "heal_settle_s": round(heal_settle, 3),
                "wills_fired": fired}

    async def _phase_roam_takeover(self) -> dict:
        roam = MQTTClient(client_id="geo-roam", version=5,
                          clean_start=False, session_expiry=3600)
        await roam.connect("127.0.0.1", self.brokers["eu"].test_port)
        self._clients.append(roam)
        await roam.subscribe(("geo/roam/#", 1))
        ok = await self._poll(
            lambda: bool(self.mgrs["us"].routes.nodes_for("geo/roam/x"))
            and "geo-roam" in self.mgrs["us"].sessions.ledger, 20.0)
        if ok < 0:
            raise RuntimeError("geoday: roam session never replicated")
        sent, got = self._stream("roam")
        pub = self.pubs["us"]
        for i in range(self.roam_msgs // 2):
            payload = f"r-a-{i}-".encode() + PAYLOAD
            await pub.publish("geo/roam/m", payload, qos=1)
            sent.add(payload)
        await self._drain_into(roam, got, idle=0.5)
        # the client roams: drop the eu attachment mid-stream, keep
        # publishing into the gap, re-attach in us via the epoch-
        # fenced takeover
        await roam.close()
        for i in range(self.roam_msgs // 2):
            payload = f"r-b-{i}-".encode() + PAYLOAD
            await pub.publish("geo/roam/m", payload, qos=1)
            sent.add(payload)
        t0 = time.perf_counter()
        roam_us = MQTTClient(client_id="geo-roam", version=5,
                             clean_start=False, session_expiry=3600)
        await roam_us.connect("127.0.0.1",
                              self.brokers["us"].test_port)
        self._clients.append(roam_us)
        self.sheet["takeover_recovery_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)
        self.sheet["takeover_session_present"] = bool(
            roam_us.session_present)
        settle = await self._settle(roam_us, "roam", self.settle_s)
        return {"pubacked": len(sent), "settle_s": round(settle, 3),
                "session_present": bool(roam_us.session_present)}

    # -- scoring -------------------------------------------------------

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
        check(self.sheet.get("wills_fired") == 1,
              f"will must fire exactly once, fired "
              f"{self.sheet.get('wills_fired')}")
        check(self.sheet.get("wills_delivered") == 1,
              f"will must be delivered exactly once, saw "
              f"{self.sheet.get('wills_delivered')}")
        check(self.sheet.get("share_duplicates") == 0,
              "$share job stream saw duplicate deliveries")
        check(bool(self.sheet.get("outage_session_present")),
              "healed-region reconnect lost the session")
        check(bool(self.sheet.get("takeover_session_present")),
              "roam takeover lost the session")
        # false flaps: every up->down transition on a link between two
        # HEALTHY shaped regions, plus ap-link flaps beyond the outage
        # itself — a 150ms link that never flaps is the whole point
        flaps = self._link_flaps()
        healthy = sum(v - self._flap_base.get(k, 0)
                      for k, v in flaps.items() if "ap" not in k)
        ap_extra = sum(v - self._flap_base.get(k, 0)
                       for k, v in flaps.items() if "ap" in k) \
            - self._ap_flap_allowance
        self.sheet["false_link_flaps"] = healthy + max(ap_extra, 0)
        check(self.sheet["false_link_flaps"] == 0,
              f"healthy shaped links flapped "
              f"{self.sheet['false_link_flaps']}x")
        # RTT-relative bounds: heal and takeover budgets scale with
        # the slowest configured link, not wall-clock guesswork
        heal_budget = (5000.0 + 60.0 * self.max_rtt_ms)
        self.sheet["heal_budget_ms"] = heal_budget
        check(0 <= self.sheet.get("heal_convergence_ms", -1)
              <= heal_budget,
              f"heal convergence "
              f"{self.sheet.get('heal_convergence_ms')}ms outside "
              f"(0, {heal_budget}ms]")
        takeover_budget = (2000.0 + 30.0 * self.max_rtt_ms)
        self.sheet["takeover_budget_ms"] = takeover_budget
        check(0 <= self.sheet.get("takeover_recovery_ms", -1)
              <= takeover_budget,
              f"roam takeover "
              f"{self.sheet.get('takeover_recovery_ms')}ms outside "
              f"(0, {takeover_budget}ms]")
        check(0 <= self.sheet.get("outage_takeover_recovery_ms", -1)
              <= takeover_budget,
              f"outage takeover "
              f"{self.sheet.get('outage_takeover_recovery_ms')}ms "
              f"outside (0, {takeover_budget}ms]")
        self.sheet["rtt_adaptive_extended"] = sum(
            m.rtt_adaptive_extended for m in self.mgrs.values())
        self.sheet["shape_deferrals"] = sum(
            m.shape_deferrals for m in self.mgrs.values())
        self.sheet["shape_drops_in"] = sum(
            m.shape_drops_in for m in self.mgrs.values())
        self.sheet["fwd_parked_rehomed"] = sum(
            m.fwd_parked_rehomed for m in self.mgrs.values())
        self.sheet["blips_detected"] = sum(
            m.blips_detected for m in self.mgrs.values())
        self.sheet["blip_resyncs"] = sum(
            m.blip_resyncs for m in self.mgrs.values())
        check(self.sheet["rtt_adaptive_extended"] > 0,
              "RTT-adaptive deadlines never engaged")
        check(self.sheet["shape_deferrals"] > 0,
              "the WAN shape never deferred a single item")
        self.sheet["violations"] = violations
        self.sheet["pass"] = not violations

    # -- entry point ---------------------------------------------------

    async def run(self) -> dict:
        t0 = time.perf_counter()
        try:
            await self._boot()
            await self._phase("shape_links", self._phase_shape_links)
            await self._phase("regional_fanin",
                              self._phase_regional_fanin)
            await self._phase("cross_region_share",
                              self._phase_cross_region_share)
            await self._phase("region_outage_heal",
                              self._phase_region_outage_heal)
            await self._phase("roam_takeover",
                              self._phase_roam_takeover)
            self._score()
        finally:
            await self._teardown()
            faults.clear()
        self.sheet["day_s"] = round(time.perf_counter() - t0, 2)
        return self.sheet
