#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic
mix and per-layer metrics are files found by the names written there
(``perfbench/README.md``). This process holds the chip: a broker built by
``bootstrap.run_server`` from a ``Config`` with every matcher knob at its
default restores the configuration's table from a sqlite store written
from ``--seed``, compiles it for the device at boot and serves its TCP
listener. The load comes from ``loadgen.py`` children that never import
JAX. After a warm-up (counted as set-up) the window runs for
``--seconds``; what was delivered is then checked against the plain
reference and one JSON object is printed as the last line. Without a TPU
the run exits non-zero before it prints a result; ``--rehearse`` lets the
CPU through, with the table size of ``perfbench/rehearse/<config>.json``,
for the sandbox only.
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # process start, as near as Python gets

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import check  # noqa: E402
import endtoend  # noqa: E402
import generators  # noqa: E402
import readers  # noqa: E402
import xplane  # noqa: E402

GENERATORS = 4                  # load-generator processes
WARM_SECONDS = 3.0              # the cell's own traffic, before the window
LEAD_SECONDS = 1.5              # a phase is told its start this far ahead
TRACE_SAMPLE_N, TRACE_RING = 8, 1 << 16
# zero over the whole run, or the run is not correct
NEVER = ("error_fallbacks", "refresh_failures", "matcher_degrades",
         "bg_refresh_errors")


def say(msg: str) -> None:
    print(f"[perfbench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


class CompileWatch:
    """Programs JAX built or loaded (``backend_compile_duration`` fires
    once per jitted shape, cache hit or not) and persistent-cache
    traffic, through jax.monitoring."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.programs = 0
        self.seconds = 0.0
        self.cache_requests = 0
        self.cache_hits = 0
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


def counters(broker) -> dict:
    """Every counter that says who answered a topic."""
    sup = broker.matcher
    batcher = sup.inner
    engine = batcher.engine
    out = {k: getattr(sup, k) for k in
           ("deadline_fallbacks", "breaker_fallbacks", "breaker_trips",
            "error_fallbacks", "refresh_failures")}
    out["matcher_degrades"] = broker.matcher_degrades
    out["bg_refresh_errors"] = engine.bg_refresh_errors
    for k in ("batches", "batched_topics", "bypasses", "cache_hits",
              "errors"):
        out[k] = getattr(batcher, k)
    for k in ("matches", "host_matches", "fallbacks"):
        out[k] = getattr(engine, k)
    out["trie_routed"] = getattr(engine, "trie_routed", 0)
    return out


# -- the cell's files -----------------------------------------------------


def load_cell(name: str, rehearse: bool) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    conf_path = os.path.join(ROOT, conf_entry["file"])
    with open(conf_path) as fh:
        config = json.load(fh)
    if rehearse:
        over = os.path.join(HERE, "rehearse", f"{cell['config']}.json")
        with open(over) as fh:
            config.update(json.load(fh))
        conf_path = None        # the generators get the merged copy
    traffic_path = os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    with open(traffic_path) as fh:
        traffic = json.load(fh)

    def mine(metric: dict) -> bool:
        return name in metric.get("workloads", [name])
    return {"name": name, "chips": cell["chips"], "config": config,
            "config_path": conf_path, "traffic": traffic,
            "traffic_path": traffic_path,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


# -- the table ------------------------------------------------------------

_SUB = ('{{"client_id": "{cid}", "filter": "{filt}", "qos": {qos}, '
        '"no_local": false, "retain_as_published": false, '
        '"retain_handling": 0, "identifier": 0, "options": ""}}')


def write_store(path: str, config: dict, seed: int, plan: dict) -> int:
    """The sqlite store a broker with this table would have left behind,
    in the StorageHook's own buckets and keys: one SubscriptionRecord per
    stored filter (client ``cl-<i>``, QoS ``i % 3``; their session records
    are left out, see the configuration's ``reduced``), and the live
    subscribers as persistent sessions: a ClientRecord each and their
    SubscriptionRecords, so that they come back with ``clean_start = 0``
    and no SUBSCRIBE stales the table. Returns the subscriptions written."""
    from maxmq_tpu.hooks.storage import ClientRecord, SQLiteStore
    table = config["table"]
    filters = generators.find(table["recipe"])(
        table["subscriptions"], seed, **table.get("args", {}))
    store = SQLiteStore(path, synchronous="OFF")
    try:
        for lo in range(0, len(filters), 50_000):
            store.apply_batch([
                ("put", "subscriptions", f"cl-{i}|{filters[i]}",
                 _SUB.format(cid=f"cl-{i}", filt=filters[i], qos=i % 3))
                for i in range(lo, min(lo + 50_000, len(filters)))])
        ops = []
        for cid, subs in plan.items():
            rec = ClientRecord(client_id=cid, listener="tcp", clean=False,
                               protocol_version=4)
            ops.append(("put", "clients", cid, rec.to_json()))
            for filt, qos in subs:
                ops.append(("put", "subscriptions", f"{cid}|{filt}",
                            _SUB.format(cid=cid, filt=filt, qos=qos)))
        store.apply_batch(ops)
    finally:
        store.close()
    return len(filters) + sum(len(v) for v in plan.values())


# -- the load generators ----------------------------------------------------


class Generators:
    """The parent's handle on the load-generator processes."""

    def __init__(self, failures: list) -> None:
        self.failures = failures
        self.procs: list = []

    async def start(self, n: int, port: int, seed: int, traffic: str,
                    config: str) -> list[dict]:
        for k in range(n):
            self.procs.append(await asyncio.create_subprocess_exec(
                sys.executable, os.path.join(HERE, "loadgen.py"),
                "--port", str(port), "--seed", str(seed),
                "--traffic", traffic, "--config", config,
                "--index", str(k), "--of", str(n),
                stdin=asyncio.subprocess.PIPE,
                stdout=asyncio.subprocess.PIPE, limit=1 << 24))
        return [await self._reply(p, "start", 90) for p in self.procs]

    async def ask(self, cmds: list[str], timeout: float) -> list[dict]:
        """One command line to each process; their replies."""
        for p, cmd in zip(self.procs, cmds):
            p.stdin.write(cmd.encode() + b"\n")
            await p.stdin.drain()
        return list(await asyncio.gather(
            *(self._reply(p, cmd, timeout)
              for p, cmd in zip(self.procs, cmds))))

    async def _reply(self, proc, cmd: str, timeout: float) -> dict:
        line = await asyncio.wait_for(proc.stdout.readline(), timeout)
        if not line:
            raise RuntimeError(f"a load generator died at {cmd!r} "
                               f"(rc {proc.returncode})")
        reply = json.loads(line)
        self.failures += reply.pop("failures", [])
        return reply

    async def stop(self) -> None:
        for p in self.procs:
            if p.returncode is None:
                try:
                    p.stdin.write(b"quit\n")
                    await p.stdin.drain()
                    await asyncio.wait_for(p.wait(), 20)
                except (asyncio.TimeoutError, ConnectionError, OSError):
                    p.kill()
                    await p.wait()


class Served:
    """A broker booted through ``bootstrap.run_server`` on the cell's
    table, and the generators connected to it."""

    def __init__(self, cell: dict, seed: int, workdir: str, traced: bool,
                 failures: list) -> None:
        self.cell, self.seed, self.workdir = cell, seed, workdir
        self.traced, self.failures = traced, failures
        self.gens = Generators(failures)
        self.stop_event = None
        self.server = None
        self.broker = self.engine = self.batcher = None
        self.seconds: dict = {}
        live = cell["config"]["live"]
        self.plan, self.groups, _hits = generators.find(live["recipe"])(
            seed, **live.get("args", {}))

    def fail(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            say(f"FAIL: {what}")

    async def boot(self) -> None:
        from maxmq_tpu.bootstrap import new_logger_from_config, run_server
        from maxmq_tpu.utils.config import Config
        config = self.cell["config"]
        t0 = time.monotonic()
        store_path = os.path.join(self.workdir, "store.db")
        n_subs = write_store(store_path, config, self.seed, self.plan)
        self.seconds["store_write"] = time.monotonic() - t0
        say(f"{n_subs} subscriptions in {store_path} "
            f"({self.seconds['store_write']:.1f} s)")
        # every matcher knob at its default: the configuration's
        # overrides name addresses, the store and the log level only
        overrides = dict(config["broker"], storage_path=store_path)
        if self.traced:
            overrides.update(trace_sample_n=TRACE_SAMPLE_N,
                             trace_slow_ms=0.0, trace_ring=TRACE_RING)
        conf = Config(**overrides)
        ready, self.stop_event, built = asyncio.Event(), asyncio.Event(), []
        t0 = time.monotonic()
        self.server = asyncio.ensure_future(run_server(
            conf, new_logger_from_config(conf), ready=ready,
            stop=self.stop_event, broker_out=built))
        waiter = asyncio.ensure_future(ready.wait())
        await asyncio.wait({self.server, waiter},
                           return_when=asyncio.FIRST_COMPLETED)
        if self.server.done():
            waiter.cancel()
            self.server.result()        # raises what serve() raised
            raise RuntimeError("run_server returned before it was ready")
        self.seconds["time_to_serve"] = time.monotonic() - t0
        self.broker = broker = built[0]
        self.batcher = broker.matcher.inner
        self.engine = self.batcher.engine
        self.fail(broker.topics.subscription_count == n_subs,
                  f"restored {broker.topics.subscription_count} "
                  f"subscriptions, wrote {n_subs}")
        self.fail(not self.engine._stale(), "boot left the tables stale")
        say(f"served after {self.seconds['time_to_serve']:.1f} s: "
            f"{ {k: round(v, 2) for k, v in broker.boot_seconds.items()} } "
            f"refresh {getattr(self.engine, 'refresh_seconds', {})} "
            f"warm {self.engine.warm_seconds:.2f}")

    async def connect(self) -> None:
        cell = self.cell
        conf_path = cell["config_path"]
        if conf_path is None:           # rehearsal: the merged copy
            conf_path = os.path.join(self.workdir, "config.json")
            with open(conf_path, "w") as fh:
                json.dump(cell["config"], fh)
        port = self.broker.listeners.get("tcp")._server.sockets[0] \
            .getsockname()[1]
        version = self.engine.index.sub_version
        replies = await self.gens.start(GENERATORS, port, self.seed,
                                        cell["traffic_path"], conf_path)
        resumed = sum(r.get("sessions_resumed", 0) for r in replies)
        self.fail(resumed == len(self.plan),
                  f"{resumed} of {len(self.plan)} live sessions resumed")
        self.fail(self.engine.index.sub_version == version,
                  "the live sessions' return changed the table")

    async def phase(self, tag: str, seconds: float, extra: str = "",
                    during=None) -> tuple:
        """One phase of the cell's traffic on every generator, starting
        together LEAD_SECONDS from now. ``during(t0_ns)`` runs beside
        it. Returns (t0_ns, the generators' replies, their dumps, what
        ``during`` returned)."""
        t0 = time.monotonic_ns() + int(LEAD_SECONDS * 1e9)
        outs = [os.path.join(self.workdir, f"{tag}-{k}.pkl")
                for k in range(GENERATORS)]
        grace = self.cell["traffic"]["drain_grace_s"]
        side = asyncio.ensure_future(during(t0)) if during else None
        replies = await self.gens.ask(
            [f"phase {t0} {seconds} {out} {extra}" for out in outs],
            LEAD_SECONDS + seconds + grace + 60)
        beside = await side if side is not None else None
        dumps = []
        for path in outs:
            with open(path, "rb") as fh:
                dumps.append(pickle.load(fh))
            os.unlink(path)
        return t0, replies, dumps, beside

    async def shutdown(self) -> None:
        # the broker first: once its $SYS ticker is gone, the clients'
        # leaving matches no topic and sets off no rotation
        if self.stop_event is not None:
            self.stop_event.set()
            await self.server
        await self.gens.stop()
        if self.engine is not None:
            # leave only after every background compile has ended
            await asyncio.get_running_loop().run_in_executor(
                None, self.engine.close, 400.0)


# -- the traced slice -------------------------------------------------------


async def trace_slice(trace_dir: str, start_ns: int, end_ns: int) -> float:
    """A profiler trace from ``start_ns`` to ``end_ns`` on the host's
    monotonic clock; starting and stopping run on a thread so that the
    broker's loop goes on. Returns the seconds it was open."""
    import jax
    loop = asyncio.get_running_loop()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the device and XLA's own spans
    opts.host_tracer_level = 1
    await asyncio.sleep(max(0.0, (start_ns - time.monotonic_ns()) / 1e9))
    await loop.run_in_executor(
        None, lambda: jax.profiler.start_trace(trace_dir,
                                               profiler_options=opts))
    opened = time.monotonic_ns()
    await asyncio.sleep(max(0.0, (end_ns - time.monotonic_ns()) / 1e9))
    closed = time.monotonic_ns()
    await loop.run_in_executor(None, jax.profiler.stop_trace)
    return (closed - opened) / 1e9


def breakdown(trace: dict, ring: list) -> dict:
    """The device operations that took most time, and where a sampled
    publish's time went on the host: each stage's summed span time over
    the sampled publishes of the window (seconds), largest first. Host
    spans are not on the profiler's clock yet, so an idle gap cannot be
    given to what the host was doing in it; the longest gaps are listed
    as they are."""
    ops = sorted(((n, s) for n, (_c, s) in trace["ops"].items()),
                 key=lambda kv: -kv[1])[:10]
    stages: dict = {}
    for entry in ring:
        for span in entry["spans"]:
            stages[span["stage"]] = (stages.get(span["stage"], 0.0)
                                     + span["dur_us"] / 1e6)
    host = sorted(stages.items(), key=lambda kv: -kv[1])[:6]
    gaps = [[f"idle_gap_{k}", s] for k, (_t, s) in
            enumerate(trace["gaps"][:4])]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[f"host_stage_{n}", s] for n, s in host] + gaps}


# -- the run ----------------------------------------------------------------


async def measure(args, cell: dict, device: dict, watch: CompileWatch,
                  workdir: str, result: dict) -> None:
    import jax
    failures = result["failures"]
    served = Served(cell, args.seed, workdir, bool(args.trace), failures)
    try:
        await served.boot()
        await served.connect()
        broker, engine = served.broker, served.engine
        say(f"warm-up: {WARM_SECONDS} s of the cell's own traffic")
        await served.phase("warm", WARM_SECONDS)
        # nothing may be building when the window opens
        await asyncio.get_running_loop().run_in_executor(
            None, engine.close, 400.0)
        served.fail(not engine._stale() and not engine.compiling,
                    "tables stale or compiling after the warm-up")
        version = engine.index.sub_version
        programs = watch.programs
        trace_dir = os.path.join(workdir, "trace")

        async def beside(t0: int) -> tuple:
            """The window's two ends as the broker sees them: counters
            and the tracer's clock at each, programs built between them,
            and the seconds the profiler's trace was open."""
            t1 = t0 + int(args.seconds * 1e9)
            tracing = None
            if args.trace:      # the whole window: few batches reach
                tracing = asyncio.ensure_future(    # the chip in a second
                    trace_slice(trace_dir, t0, t1))
            ends = []
            for edge in (t0, t1):
                await asyncio.sleep(
                    max(0.0, (edge - time.monotonic_ns()) / 1e9))
                ends.append((counters(broker), broker.tracer.clock()))
            built = watch.programs - programs
            return ends, built, await tracing if tracing else 0.0

        t0, replies, dumps, (ends, built, slice_s) = await served.phase(
            "window", args.seconds, during=beside)
        t1 = t0 + int(args.seconds * 1e9)
        after = counters(broker)
        say(f"window done; generators: {replies}")
        say(f"counters over the window: "
            f"{ {k: ends[1][0][k] - ends[0][0][k] for k in after} }")
        served.fail(built == 0,
                    f"{built} programs built inside the window")
        served.fail(engine.index.sub_version == version
                    and not engine._stale(),
                    "the table changed during the window")
        for k in NEVER:
            served.fail(after[k] == 0, f"{k} = {after[k]} over the run")
        stats = [d.memory_stats() or {} for d in jax.devices()]
        device["memory_peak_bytes"] = max(
            (s.get("peak_bytes_in_use") or 0) for s in stats)
        joined = check.join(dumps, served.groups, t0, t1)
        failures += joined["failures"]
        say(f"checked {joined['messages']} messages, "
            f"{joined['deliveries']} right deliveries "
            f"({joined['in_window']} inside the window), attempted "
            f"{joined['attempted']}, failed {joined['failed']}; latency "
            f"samples {len(joined['latencies_ns'])}")
        served.fail(joined["deliveries"] > 0, "nothing was delivered")
        result.update(attempted=joined["attempted"], failed=joined["failed"])
        run = {"seconds": args.seconds, "joined": joined,
               "window_opens_s": t0 / 1e9 - T_START,
               "counters": (ends[0][0], ends[1][0]),
               "boot_seconds": dict(
                   broker.boot_seconds, bucket_warm=engine.warm_seconds,
                   store_write=served.seconds["store_write"],
                   time_to_serve=served.seconds["time_to_serve"],
                   **{f"refresh_{k}": v for k, v in getattr(
                       engine, "refresh_seconds", {}).items()}),
               "generators": replies, "device_kind": device["kind"],
               "kernel": {"plan": engine.kernel_plan,
                          "max_rows": getattr(engine, "fixed_max_rows", 7)}
               if getattr(engine, "kernel_plan", None) else None}
        if args.trace:
            c0, c1 = ends[0][1] // 1000, ends[1][1] // 1000
            run["ring"] = [e for e in broker.tracer.report()["entries"]
                           if c0 <= e["start_us"] < c1]
            say(f"tracer ring: {len(run['ring'])} sampled publishes of "
                "the window")
            path = xplane.find(trace_dir)
            served.fail(path is not None, "the profiler wrote no trace")
            if path is not None:
                run["trace"] = xplane.reduce(path, slice_s, cell["chips"])
                device["busy_s"] = run["trace"]["busy_s"]
                device["window_s"] = run["trace"]["window_s"]
                result["breakdown"] = breakdown(run["trace"], run["ring"])
                if args.keep_trace:
                    shutil.copy(path, args.keep_trace)
        result["run"] = run
    finally:
        await served.shutdown()


def metrics_of(cell: dict, run: dict, traced: bool) -> dict:
    out = {}
    if not traced:
        for m in cell["end_to_end"]:
            out[m["name"]] = {"value": getattr(endtoend, m["name"])(run),
                              "unit": m["unit"]}
        return out
    for m in cell["per_layer"]:
        with open(os.path.join(HERE, "layers", f"{m['name']}.json")) as fh:
            layer = json.load(fh)
        value = getattr(readers, layer["reader"])(run, **layer["args"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="let the CPU through, at the rehearsal's table "
                         "size: for the sandbox, never a measurement")
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="copy the traced run's .xplane.pb there")
    args = ap.parse_args()
    cell = load_cell(args.workload, args.rehearse)

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
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] < cell["chips"]):
        print(f"perfbench: the cell needs {cell['chips']} TPU chip(s), JAX "
              f"found {device}; nothing was run", file=sys.stderr)
        return 3
    say(f"cell {cell['name']} seed {args.seed}: device {device}; compile "
        f"cache at {cache_dir}")

    import faulthandler
    import signal
    faulthandler.register(signal.SIGTERM, all_threads=True, chain=True)

    result: dict = {"failures": [], "attempted": 0, "failed": 0}
    watch = CompileWatch()
    workdir = tempfile.mkdtemp(prefix="perfbench-")
    try:
        from maxmq_tpu.bootstrap import install_event_loop
        from maxmq_tpu.utils.config import Config
        install_event_loop(Config().broker_event_loop)   # as `maxmq start`
        asyncio.run(measure(args, cell, device, watch, workdir, result))
    except Exception as exc:
        import traceback
        traceback.print_exc()
        result["failures"].append(f"run raised {exc!r}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if device["platform"] != "tpu":
        result["failures"].append(f"platform is {device['platform']}, "
                                  "not tpu")
    say(f"programs {watch.programs} ({watch.seconds:.1f} s of XLA), cache "
        f"{watch.cache_hits}/{watch.cache_requests} hits; failures: "
        f"{result['failures']}")
    run = result.get("run")
    if run is None:
        return 1                # nothing measured: no result line
    line = {"correct": not result["failures"],
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics_of(cell, run, bool(args.trace)),
            "device": device}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
