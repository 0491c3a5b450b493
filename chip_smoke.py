#!/usr/bin/env python3
"""Proof that the served path starts and answers correctly on the chip.

One process holds the chip: a broker built by ``bootstrap.run_server``
from a ``Config`` with every matcher knob at its default (``matcher =
"sig"``, supervised, 256-topic micro-batches) restores a 1,000,000-filter
table from a sqlite store, compiles it for the device at boot, and then
serves MQTT clients over its TCP listener. The clients live in a child
process that never imports JAX (this file again, as ``--drive PORT``), so
the broker's event loop carries the broker alone. Two waves of PUBLISHes
go through listener -> decode -> MicroBatcher -> fused kernel -> native
decode -> fan-out -> writer: wave A right after the live SUBSCRIBEs (the
overlay window, while the table they staled recompiles in the
background), wave B after that rotation has landed. What every subscriber
received is checked against the CPU trie; the counters that say *who* answered (device, bypass,
host probe, overflow, supervisor fallbacks) are printed per wave; and a
sample of topics goes through the kernel on the full table and must equal
the trie.

    python chip_smoke.py                # one chip, as the driver runs it
    python chip_smoke.py --chips 4      # matcher_mesh = "1x4", four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --subs 20000   # CPU rehearsal

The last line of stdout is one JSON object, ``{"ok": ..., "device":
{...}}``; everything else is reported on earlier lines. A run without
``--subs`` that finds no accelerator exits non-zero before it prints any
result. The CPU rehearsal runs every phase and fails on the platform
alone. Nothing here is a speed of the system: the seconds are set-up
times and the counts are splits.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "perfbench"))

import generators  # noqa: E402  (the benchmark's corpus builder)

FULL_SUBS = 1_000_000          # BASELINE.json config 4's table
PUBLISHERS = 4                 # and 64 subscribers: see live_plan
BURST, BURSTS_PER_WAVE = 256, 4    # per publisher: 4 x 256 x 4 = 4096 a wave
SAMPLE_TOPICS = 1024           # matcher-vs-trie sample on the full table
# zero over the whole run: something broke
NEVER = ("error_fallbacks", "refresh_failures", "matcher_degrades",
         "bg_refresh_errors")
# zero wherever no table rotation shares the interpreter (first batch,
# wave B). In wave A they are printed, not enforced: a deadline is kept
# per topic from its enqueue on the event loop, the rotation's
# compile_sig holds the interpreter for seconds at a time, and five late
# topics in ten seconds open the breaker (supervisor.py) — a finding for
# the rotation's cost, which this script does not tune away
QUIET = ("deadline_fallbacks", "breaker_fallbacks", "breaker_trips")


def say(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


class Report:
    """What the run proved, and what it did not."""

    def __init__(self) -> None:
        self.failures: list[str] = []
        self.proof: dict = {}

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            say(f"FAIL: {what}")
        return bool(ok)


# -- device-side accounting ---------------------------------------------


class CompileWatch:
    """Programs JAX built or loaded (``backend_compile_duration`` fires
    once per jitted shape, cache hit or not) and persistent-cache traffic,
    through jax.monitoring. ``mark()`` returns what happened since the
    last mark."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.programs = 0
        self.seconds = 0.0
        self.cache_requests = 0
        self.cache_hits = 0
        self._last = (0, 0.0, 0, 0)
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> dict:
        now = (self.programs, self.seconds, self.cache_requests,
               self.cache_hits)
        was, self._last = self._last, now
        return {"programs": now[0] - was[0],
                "xla_seconds": round(now[1] - was[1], 3),
                "cache_requests": now[2] - was[2],
                "cache_hits": now[3] - was[3]}


def counters(broker) -> dict:
    """Every counter that says who answered a topic."""
    sup = broker.matcher
    batcher = sup.inner
    engine = batcher.engine
    out = {k: getattr(sup, k) for k in QUIET + NEVER[:2]}
    out["matcher_degrades"] = broker.matcher_degrades
    out["bg_refresh_errors"] = engine.bg_refresh_errors
    for k in ("batches", "batched_topics", "bypasses", "cache_hits",
              "errors"):
        out[k] = getattr(batcher, k)
    for k in ("matches", "host_matches", "fallbacks"):
        out[k] = getattr(engine, k)
    out["trie_routed"] = getattr(engine, "trie_routed", 0)
    return out


def compile_seconds(engine, when: str) -> dict:
    """Host seconds of the engine's newest table compile and bucket
    warm (the sharded engine times only the warm)."""
    out = {f"{when}_{k}": round(v, 2)
           for k, v in getattr(engine, "refresh_seconds", {}).items()}
    out[f"{when}_bucket_warm"] = round(engine.warm_seconds, 2)
    return out


def split(before: dict, after: dict, largest_batch: int) -> dict:
    """One wave's split. ``device`` = topics the batcher dispatched to the
    kernel; ``device_answered`` takes off those a fallback answered in
    its place (deadline, error, row overflow)."""
    d = {k: after[k] - before[k] for k in after}
    device = d["batched_topics"] - d["bypasses"]
    d["device"] = device
    d["device_answered"] = max(0, device - d["deadline_fallbacks"]
                               - d["error_fallbacks"] - d["fallbacks"])
    d["largest_batch"] = largest_batch
    return d


# -- the table ------------------------------------------------------------


def corpus(n: int, seed: int):
    """BASELINE.json config 4's shape, from the benchmark's own builder
    (perfbench/generators.py): its ``+``/``#`` mix with 10% ``$share``,
    client ``cl-<i>`` at QoS ``i % 3``; and ``topic_gen(batch, seed)``,
    that many fresh topics of the corpus's shape."""
    def topic_gen(batch: int, seed2: int) -> list[str]:
        rng = random.Random(seed2)
        return [generators.corpus_topic(rng) for _ in range(batch)]
    return generators.corpus(n, seed), topic_gen


def write_store(path: str, filters: list[str]) -> None:
    """The sqlite store a broker with this table would have left behind:
    one SubscriptionRecord per filter, in the StorageHook's own bucket
    and key scheme. Session records are left out — restoring a million
    disconnected sessions costs ~6 KB of host memory each and nothing on
    the device; the restore indexes a subscription whose session is gone
    all the same (Broker._restore_sessions)."""
    from maxmq_tpu.hooks.storage import SQLiteStore, SubscriptionRecord
    store = SQLiteStore(path, synchronous="OFF")
    try:
        for lo in range(0, len(filters), 50_000):
            ops = []
            for i in range(lo, min(lo + 50_000, len(filters))):
                cid = f"cl-{i}"
                rec = SubscriptionRecord(client_id=cid, filter=filters[i],
                                         qos=i % 3)
                ops.append(("put", "subscriptions", f"{cid}|{filters[i]}",
                            rec.to_json()))
            store.apply_batch(ops)
    finally:
        store.close()


# -- live clients -----------------------------------------------------------


def live_plan(seed: int, topic_gen) -> tuple[dict, dict, list]:
    """(subscriber id -> [(filter, qos)], share group -> member ids,
    topics that hit them). Plain '#', '+' and exact filters in the
    corpus's own namespace and in a ``live/`` one, and four ``$share``
    groups of four whose members hold no plain filter on the group's
    topics, so "once per group" can be checked member by member."""
    alphabet = [f"{c}{i}" for c in "abcdefgh" for i in range(12)]
    rng = random.Random(seed + 1)
    exact = topic_gen(16, seed + 2)      # corpus-shaped exact topics
    subs: dict[str, list] = {}
    hits: list[str] = list(exact)
    for i in range(48):
        cid, a, b = f"smoke-s{i}", alphabet[i], alphabet[i + 48]
        if i < 16:
            subs[cid] = [(f"{a}/#", i % 2), (f"live/d{i}/#", 1)]
            hits += [f"live/d{i}/state", f"live/d{i}/a/b"]
        elif i < 32:
            subs[cid] = [(f"+/{a}/+", i % 2), (f"{a}/+/{b}/#", 0),
                         ("live/+/cmd", 1), (f"live/+/cmd/{i}", 0)]
            hits += [f"{alphabet[i - 16]}/{a}/{b}", f"{a}/x/{b}/y",
                     f"live/d{i}/cmd", f"live/d{i}/cmd/{i}"]
        else:
            subs[cid] = [(exact[i - 32], i % 2), (f"live/d{i}/state", 1)]
            hits.append(f"live/d{i}/state")
    group_filters = ["live/+/telemetry", "live/+/telemetry",
                     f"{alphabet[90]}/#", f"+/+/{alphabet[91]}"]
    groups: dict[str, list] = {}
    for g, filt in enumerate(group_filters):
        members = [f"smoke-s{48 + 4 * g + m}" for m in range(4)]
        groups[f"smoke{g}"] = members
        for m, cid in enumerate(members):
            subs[cid] = [(f"$share/smoke{g}/{filt}", (g + m) % 2),
                         (f"live/inbox/{cid}", 0)]
            hits.append(f"live/inbox/{cid}")
    hits += [f"live/d{i}/telemetry" for i in range(16)]
    hits += [f"{alphabet[90]}/{rng.choice(alphabet)}/{rng.choice(alphabet)}"
             for _ in range(8)]
    hits += [f"{rng.choice(alphabet)}/{rng.choice(alphabet)}/{alphabet[91]}"
             for _ in range(8)]
    return subs, groups, hits


def wave_messages(wave: int, seed: int, topic_gen, hits: list[str]) -> list:
    """Per publisher, BURSTS_PER_WAVE bursts of BURST (topic, payload,
    qos): half from the corpus's own topic generator, half aimed at the
    live subscribers; QoS 0/1 mixed, 64-512 B payloads that open with
    ``<publisher>:<seq>|``."""
    rng = random.Random(seed * 1000 + wave)
    per_pub = BURST * BURSTS_PER_WAVE
    fresh = topic_gen(PUBLISHERS * per_pub, seed * 100 + wave)
    out = []
    for p in range(PUBLISHERS):
        msgs = []
        for k in range(per_pub):
            seq = wave * per_pub + k
            topic = (fresh[p * per_pub + k] if rng.random() < 0.5
                     else rng.choice(hits))
            head = f"{p}:{seq}|".encode()
            body = head + rng.randbytes(rng.randint(64, 512) - len(head))
            msgs.append((topic, body, rng.randint(0, 1)))
        out.append(msgs)
    return out


async def send_wave(pubs, messages) -> int:
    """The publishers take turns, a burst each: all of a burst's packets
    are on the wire before its first ack is awaited, and the next burst
    leaves when every QoS 1 PUBLISH of this one is PUBACKed (one that is
    not raises). So BURST publishes are in flight at a time, one served
    micro-batch's worth; four bursts at once (1,024 in flight) back the
    broker's event loop up past the supervisor's 250 ms deadline, which
    is a finding about the host path (PERF.md), not what this script is
    for. Returns QoS 1 publishes acked."""
    acked = 0
    for lo in range(0, len(messages[0]), BURST):
        for client, msgs in zip(pubs, messages):
            burst = msgs[lo:lo + BURST]
            await asyncio.gather(*(client.publish(t, body, qos=q,
                                                  timeout=120)
                                   for t, body, q in burst))
            acked += sum(q for _, _, q in burst)
    return acked


def drain(clients: dict) -> tuple[dict, list]:
    """({(publisher, seq): [(subscriber, delivered qos)]}, order
    violations), emptying every subscriber's queue."""
    got: dict = {}
    disorder = []
    for cid, c in clients.items():
        last: dict[int, int] = {}
        while not c.messages.empty():
            m = c.messages.get_nowait()
            head = m.payload.split(b"|", 1)[0]
            p, seq = (int(x) for x in head.split(b":"))
            if seq <= last.get(p, -1):
                disorder.append((cid, p, last[p], seq))
            last[p] = seq
            got.setdefault((p, seq), []).append((cid, m.qos))
    return got, disorder


async def settle(clients: dict, want: int, timeout: float = 120.0) -> None:
    """Wait until ``want`` deliveries are queued at the subscribers (or
    the timeout), then a moment more so that a delivery too many shows."""
    t0 = time.monotonic()
    while (sum(c.messages.qsize() for c in clients.values()) < want
           and time.monotonic() - t0 < timeout):
        await asyncio.sleep(0.05)
    await asyncio.sleep(0.5)


def check_wave(name: str, messages, got, disorder, ref,
               groups: dict) -> dict:
    """Delivered sets against the plain reference: a TopicIndex holding
    the live clients' subscriptions alone, built by this script."""
    members = {cid: g for g, ms in groups.items() for cid in ms}
    wrong = []
    deliveries = 0
    for p, msgs in enumerate(messages):
        for topic, body, qos in msgs:
            seq = int(body.split(b"|", 1)[0].split(b":")[1])
            want = ref.subscribers(topic)
            have = got.pop((p, seq), [])
            deliveries += len(have)
            plain = {cid: min(qos, sub.qos)
                     for cid, sub in want.subscriptions.items()}
            have_plain: dict = {}
            per_group: dict[str, list] = {}
            for cid, q in have:
                if cid in plain or cid not in members:
                    have_plain[cid] = q
                else:
                    per_group.setdefault(members[cid], []).append((cid, q))
            want_groups = {g: cands for (g, _f), cands in want.shared.items()}
            ok = (have_plain == plain and len(have_plain) + sum(
                      len(v) for v in per_group.values()) == len(have)
                  and per_group.keys() == want_groups.keys()
                  and all(len(v) == 1 and v[0][1] == min(
                              qos, want_groups[g][v[0][0]].qos)
                          for g, v in per_group.items()))
            if not ok:
                wrong.append((topic, sorted(have), sorted(plain),
                              sorted(want_groups)))
    failures = []
    if wrong:
        failures.append(f"wave {name}: {len(wrong)} topics delivered to "
                        f"the wrong set, first {wrong[:2]}")
    if got:
        failures.append(f"wave {name}: {len(got)} deliveries of messages "
                        "nobody published in it")
    if disorder:
        failures.append(f"wave {name}: per-publisher order broken "
                        f"{len(disorder)} times, first {disorder[:2]}")
    return {"published": sum(len(m) for m in messages),
            "delivered": deliveries, "wrong_sets": len(wrong),
            "order_violations": len(disorder), "failures": failures}


def normalize(result) -> tuple[dict, dict]:
    to_set = getattr(result, "to_set", None)
    s = to_set() if to_set is not None else result
    return ({cid: sub.qos for cid, sub in s.subscriptions.items()},
            {key: {cid: sub.qos for cid, sub in m.items()}
             for key, m in s.shared.items()})


def sample_check(report: Report, batch_fn, index, topics: list[str]) -> int:
    """The device path (``batch_fn``: the batcher's synchronous surface,
    no bypass) against the trie on the full table: subscriber ids, QoS
    and shared groups of every sampled topic."""
    bad = []
    for lo in range(0, len(topics), 256):
        chunk = topics[lo:lo + 256]
        for topic, got in zip(chunk, batch_fn(chunk)):
            if normalize(got) != normalize(index.subscribers(topic)):
                bad.append(topic)
    report.check(not bad, f"matcher != trie on {len(bad)} of "
                 f"{len(topics)} sampled topics, first {bad[:3]}")
    return len(topics)


async def wait_rotated(supervisor, engine, tick,
                       limit: float = 400.0) -> None:
    """Until the compiled tables are the live index's, no background
    compile is in flight and the breaker (which a rotation under load
    opens) has closed again. A stale table recompiles when the next
    topic is matched, and an open breaker closes on a live request that
    the device answers in time; ``tick`` publishes one. Past ``limit``
    seconds every thread's stack goes to stderr and the run fails."""
    loop = asyncio.get_running_loop()
    t0 = time.monotonic()
    while True:
        left = limit - (time.monotonic() - t0)
        if left > 0:
            await loop.run_in_executor(None, engine.close, min(left, 30.0))
        if (not engine._stale() and not engine.compiling
                and supervisor.breaker_state_name == "closed"):
            return
        if engine.compiling and left > 0:
            say(f"still compiling {limit - left:.0f} s in (index at "
                f"version {engine.index.sub_version})")
            continue
        if left <= 0:
            import faulthandler
            faulthandler.dump_traceback(all_threads=True)
            raise RuntimeError(
                f"not settled after {limit:.0f} s (stale: "
                f"{engine._stale()}, compiling: {engine.compiling}, "
                f"breaker: {supervisor.breaker_state_name}, index at "
                f"version {engine.index.sub_version})")
        await tick()
        await asyncio.sleep(0.2)


# -- the client process -------------------------------------------------------


async def drive(port: int, seed: int) -> None:
    """The child's whole life: MQTT clients over TCP, told what to do a
    line at a time on stdin, answering each line with one JSON line on
    stdout. It holds the plain reference too: a TopicIndex of the live
    subscriptions alone, built from what it SUBSCRIBEd."""
    from maxmq_tpu.matching.trie import TopicIndex
    from maxmq_tpu.mqtt_client import MQTTClient
    from maxmq_tpu.protocol.packets import Subscription

    _, topic_gen = corpus(0, seed)
    plan, groups, hits = live_plan(seed, topic_gen)
    ref = TopicIndex()
    # MQTTClient sends no PINGREQ of its own and a rotation outlasts a
    # short keepalive: an hour keeps the broker from hanging up
    pubs = [MQTTClient(f"smoke-p{p}", keepalive=3600)
            for p in range(PUBLISHERS)]
    subs = {cid: MQTTClient(cid, keepalive=3600) for cid in plan}
    for c in pubs:
        await c.connect("127.0.0.1", port, timeout=30)
    print(json.dumps({"publishers": len(pubs)}), flush=True)
    loop = asyncio.get_running_loop()
    subscribed = False
    while True:
        cmd = (await loop.run_in_executor(None, sys.stdin.readline)).strip()
        if cmd in ("", "quit"):
            break
        reply: dict = {}
        lost = [c.client_id for c in pubs + list(subs.values()) * subscribed
                if c._closed.is_set()]
        if lost:
            print(json.dumps({"failures": [
                f"driver: {len(lost)} clients lost their connection "
                f"before {cmd!r}, first {lost[:3]}"]}), flush=True)
            continue
        if cmd == "first":
            await pubs[0].publish(topic_gen(1, seed + 7)[0], b"first",
                                  qos=1, timeout=120)
        elif cmd == "tick":
            await pubs[0].publish("smoke/tick", b"tick", qos=1, timeout=60)
        elif cmd == "subscribe":
            refused = []
            for cid, c in subs.items():
                await c.connect("127.0.0.1", port, timeout=30)
                codes = await c.subscribe(*plan[cid], timeout=60)
                if any(code >= 0x80 for code in codes):
                    refused.append((cid, codes))
                for filt, qos in plan[cid]:
                    ref.subscribe(cid, Subscription(filter=filt, qos=qos))
            subscribed = True
            reply = {"subscribers": len(subs), "hits": hits,
                     "filters": sum(len(v) for v in plan.values()),
                     "failures": ([f"SUBSCRIBE refused: {refused[:3]}"]
                                  if refused else [])}
        elif cmd.startswith("wave "):
            name = cmd.split()[1]
            messages = wave_messages("AB".index(name), seed, topic_gen, hits)
            want = 0
            for msgs in messages:
                for topic, _body, _qos in msgs:
                    r = ref.subscribers(topic)
                    want += len(r.subscriptions) + len(r.shared)
            t0 = time.perf_counter()
            acked = await send_wave(pubs, messages)
            await settle(subs, want)
            took = time.perf_counter() - t0
            got, disorder = drain(subs)
            reply = check_wave(name, messages, got, disorder, ref, groups)
            reply.update(seconds=round(took, 2), qos1_acked=acked)
        else:
            reply = {"failures": [f"driver: unknown command {cmd!r}"]}
        print(json.dumps(reply), flush=True)
    for c in list(subs.values()) + pubs:
        await c.disconnect()


class Driver:
    """The parent's handle on the client process."""

    def __init__(self, report: Report) -> None:
        self.report = report
        self.proc = None

    async def start(self, port: int, seed: int) -> None:
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.abspath(__file__), "--drive", str(port),
            "--seed", str(seed), stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, limit=1 << 24)
        await self._reply("start", 60)      # its publishers are connected

    async def ask(self, cmd: str, timeout: float = 600.0) -> dict:
        self.proc.stdin.write(cmd.encode() + b"\n")
        await self.proc.stdin.drain()
        return await self._reply(cmd, timeout)

    async def _reply(self, cmd: str, timeout: float) -> dict:
        line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        if not line:
            raise RuntimeError(f"client process died at {cmd!r} "
                               f"(rc {self.proc.returncode})")
        reply = json.loads(line)
        for failure in reply.pop("failures", []):
            self.report.check(False, failure)
        return reply

    async def stop(self) -> None:
        if self.proc is None or self.proc.returncode is not None:
            return
        try:
            self.proc.stdin.write(b"quit\n")
            await self.proc.stdin.drain()
            await asyncio.wait_for(self.proc.wait(), 30)
        except (asyncio.TimeoutError, ConnectionError, OSError):
            self.proc.kill()
            await self.proc.wait()


# -- the run ----------------------------------------------------------------


async def serve_and_check(args, report: Report, watch: CompileWatch,
                          workdir: str) -> None:
    import jax

    from maxmq_tpu import native
    from maxmq_tpu.bootstrap import new_logger_from_config, run_server
    from maxmq_tpu.utils.config import Config

    seconds = report.proof.setdefault("seconds", {})
    loop = asyncio.get_running_loop()

    t0 = time.perf_counter()
    filters, topic_gen = corpus(args.subs, args.seed)
    store_path = os.path.join(workdir, "smoke.db")
    write_store(store_path, filters)
    seconds["corpus_and_store"] = round(time.perf_counter() - t0, 2)
    say(f"{len(filters)} filters in {store_path} "
        f"({seconds['corpus_and_store']} s); subscription records only, "
        "no session records")
    del filters

    # every matcher knob at its default; only addresses, the store and
    # the log level are set
    conf = Config(mqtt_tcp_address="127.0.0.1:0",
                  metrics_address="127.0.0.1:0", log_level="warn",
                  storage_backend="sqlite", storage_path=store_path,
                  matcher_mesh="1x4" if args.chips == 4 else "")
    ready, stop, built = asyncio.Event(), asyncio.Event(), []
    watch.mark()
    t0 = time.perf_counter()
    server = asyncio.ensure_future(run_server(
        conf, new_logger_from_config(conf), ready=ready, stop=stop,
        broker_out=built))
    waiter = asyncio.ensure_future(ready.wait())
    await asyncio.wait({server, waiter}, return_when=asyncio.FIRST_COMPLETED)
    if server.done():
        waiter.cancel()
        server.result()         # raises what serve() raised
        raise RuntimeError("run_server returned before it was ready")
    seconds["boot"] = round(time.perf_counter() - t0, 2)
    broker = built[0]
    engine = broker.matcher.inner.engine
    batcher = broker.matcher.inner
    driver = Driver(report)
    try:
        boot = watch.mark()
        seconds["restore_index_build"] = round(
            broker.boot_seconds["restore"], 2)
        seconds["boot_matcher_compile"] = round(
            broker.boot_seconds["matcher_compile"], 2)
        seconds.update(compile_seconds(engine, "boot"))
        seconds["boot_xla"] = boot["xla_seconds"]
        report.proof["boot_programs"] = boot
        say(f"served after {seconds['boot']} s: {seconds} {boot}")
        report.check(broker.topics.subscription_count == args.subs,
                     f"restored {broker.topics.subscription_count} "
                     f"subscriptions, wrote {args.subs}")
        report.check(not engine._stale(), "boot left the tables stale")
        if args.chips == 4:
            shards = engine._state[2]
            placed = [sorted(str(d) for d in a.sharding.device_set)
                      for a in shards]
            per_dev = [[str(s.device) for s in a.addressable_shards]
                       for a in shards]
            report.proof["shard_devices"] = per_dev[5]   # the planes
            report.check(all(len(p) == 4 for p in placed)
                         and len(set(per_dev[5])) == 4,
                         f"table shards not on four devices: {placed}")
        else:
            report.proof["pallas_active"] = engine.pallas_active
            report.proof["kernel_plan"] = engine.kernel_plan
            report.check(engine.pallas_active,
                         "fused kernel not active (XLA body serving)")
        lib = native.available()
        decode = native.decode_module(build=False)
        report.proof["native"] = {
            "lib": lib, "decode": decode is not None,
            "intents": hasattr(decode, "decode_batch_intents")}
        report.check(lib and decode is not None
                     and hasattr(decode, "decode_batch_intents"),
                     "native library / maxmq_decode not loaded")

        port = broker.listeners.get("tcp")._server.sockets[0] \
            .getsockname()[1]
        await driver.start(port, args.seed)

        # the first batch through the served path, before anything
        # stales the boot tables
        watch.mark()
        t0 = time.perf_counter()
        await driver.ask("first")
        while not engine.matches:       # nobody to deliver it to: the
            await asyncio.sleep(0.001)  # engine's own count says "decoded"
        seconds["first_batch"] = round(time.perf_counter() - t0, 4)
        report.proof["first_batch_programs"] = watch.mark()
        say(f"first batch decoded {seconds['first_batch']} s after its "
            f"PUBLISH left: {report.proof['first_batch_programs']}")

        before = counters(broker)
        for k in QUIET:
            report.check(before[k] == 0, f"{k} = {before[k]} before any "
                         "table rotation")
        report.check(before["batched_topics"] > before["bypasses"],
                     "the first batch did not go to the device")
        batcher.largest_batch = 0
        live = await driver.ask("subscribe")
        hits = live.pop("hits")
        on_thread = (f"{batcher.device_round_trip * 1e3:.3f} ms"
                     if batcher.device_round_trip
                     else "not measured (tracing is off)")
        say(f"over TCP from pid {driver.proc.pid}: {live}; device round "
            f"trip as the loop sees it, executor hops included (drives "
            f"the bypass) {batcher.device_rtt * 1e3:.2f} ms; on the "
            f"executor thread {on_thread}")

        waves = report.proof.setdefault("waves", {})
        for name in "AB":
            watch.mark()
            checked = await driver.ask(f"wave {name}")
            after = counters(broker)
            wave = split(before, after, batcher.largest_batch)
            wave.update(checked, compiles=watch.mark(),
                        stale_at_end=engine._stale(),
                        device_rtt_ms=round(batcher.device_rtt * 1e3, 3))
            waves[name] = wave
            say(f"wave {name}: {json.dumps(wave)}")
            if not wave["device_answered"]:
                # not a failure of the path: the adaptive bypass is free
                # to find the host probe cheaper for every batch of a
                # wave (PERF.md, S4); what is enforced is below
                say(f"finding: in wave {name} the device answered no "
                    "topic in time")
            if name == "A":
                t0 = time.perf_counter()
                await wait_rotated(broker.matcher, engine,
                                   lambda: driver.ask("tick"))
                seconds["rotation_wait"] = round(
                    time.perf_counter() - t0, 2)
                seconds.update(compile_seconds(engine, "rotation"))
                report.proof["rotation_programs"] = watch.mark()
                say(f"rotation landed after {seconds['rotation_wait']} s "
                    f"more: {report.proof['rotation_programs']}")
                before = counters(broker)
                batcher.largest_batch = 0
        for k in QUIET:
            report.check(waves["B"][k] == 0,
                         f"wave B: {k} = {waves['B'][k]}")
        report.check(waves["B"]["compiles"]["programs"] == 0,
                     f"wave B compiled: {waves['B']['compiles']}")
        report.check(not waves["B"]["stale_at_end"],
                     "wave B ran on stale tables")
        # the chip did work that was used: the first batch always goes
        # to it (no round trip measured yet), and so does whatever of
        # the waves the bypass leaves it
        served = 1 + sum(w["device_answered"] for w in waves.values())
        report.proof["device_answered_served"] = served

        # ... and on the fresh table its answers are the trie's: every
        # sampled topic through the kernel and the native decode
        t0 = time.perf_counter()
        sample = topic_gen(SAMPLE_TOPICS, args.seed + 9) + hits
        was = counters(broker)
        report.proof["sampled_topics"] = await loop.run_in_executor(
            None, sample_check, report, batcher.subscribers_batch,
            broker.topics, sample)
        now = counters(broker)
        through_kernel = ((now["matches"] - was["matches"])
                          - (now["host_matches"] - was["host_matches"])
                          - (now["trie_routed"] - was["trie_routed"]))
        report.check(through_kernel >= len(sample),
                     f"only {through_kernel} of {len(sample)} sampled "
                     "topics went through the kernel")
        seconds["sample_check"] = round(time.perf_counter() - t0, 2)

        total = counters(broker)
        report.proof["run_totals"] = total
        for k in NEVER:
            report.check(total[k] == 0, f"{k} = {total[k]} over the run")
        stats = [d.memory_stats() or {} for d in jax.devices()]
        report.proof["peak_bytes_in_use"] = [
            s.get("peak_bytes_in_use") for s in stats]

    finally:
        # the broker first: once its $SYS ticker is gone, the clients'
        # leaving (which stales the tables once more) matches no topic
        # and so sets off no last rotation
        stop.set()
        await server
        await driver.stop()
        # leave only after every background compile has ended
        await loop.run_in_executor(None, engine.close, 400.0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--subs", type=int, default=None,
                    help="table size, for the CPU rehearsal only "
                         f"(default {FULL_SUBS:,})")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve through matcher_mesh = \"1x4\" and run "
                         "no one-chip phase")
    ap.add_argument("--drive", type=int, metavar="PORT",
                    help=argparse.SUPPRESS)    # the client process's role
    args = ap.parse_args()
    if args.drive:
        asyncio.run(drive(args.drive, args.seed))
        return 0
    rehearsal = args.subs is not None
    args.subs = args.subs or FULL_SUBS

    # fresh native libraries from the committed sources, before the
    # package (which loads them once per process) is imported
    subprocess.run(["make", "-C", os.path.join(ROOT, "native")], check=True,
                   stdout=sys.stderr)

    from maxmq_tpu.accel import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not rehearsal:
        print(f"chip_smoke: JAX found no accelerator ({device}); "
              "nothing was run", file=sys.stderr)
        return 3
    warm = os.path.isdir(cache_dir) and os.listdir(cache_dir)
    say(f"device {device}; compile cache at {cache_dir} "
        f"({'warm' if warm else 'cold'})")

    import faulthandler
    import signal
    faulthandler.register(signal.SIGTERM, all_threads=True, chain=True)

    report = Report()
    report.check(device["platform"] == "tpu",
                 f"platform is {device['platform']}, not tpu")
    report.check(device["count"] == args.chips,
                 f"{device['count']} devices, --chips {args.chips}")
    watch = CompileWatch()
    workdir = tempfile.mkdtemp(prefix="maxmq-smoke-")
    t0 = time.perf_counter()
    try:
        from maxmq_tpu.bootstrap import install_event_loop
        from maxmq_tpu.utils.config import Config
        install_event_loop(Config().broker_event_loop)   # as `maxmq start`
        asyncio.run(serve_and_check(args, report, watch, workdir))
    except Exception as exc:
        import traceback
        traceback.print_exc()
        report.check(False, f"run raised {exc!r}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    watch.mark()
    report.proof.update(
        device=device, subs=args.subs, seed=args.seed, chips=args.chips,
        compile_cache={"dir": cache_dir, "requests": watch.cache_requests,
                       "hits": watch.cache_hits},
        programs_total=watch.programs,
        xla_seconds_total=round(watch.seconds, 2),
        wall_seconds=round(time.perf_counter() - t0, 2),
        failures=report.failures)
    ok = not report.failures
    print(json.dumps({"proof": report.proof}), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
