"""End-to-end broker throughput: the mqtt-stresser scenario.

Mirrors the reference engine's published benchmark setup
(vendor/github.com/mochi-co/mqtt/v2/README.md:372-396, mqtt-stresser
``-num-clients=N -num-messages=10000``): N clients; each subscribes to
its own topic, publishes M QoS0 messages to it, and receives them all
back. Reports aggregate + median per-client publish and receive rates —
the same tool-relative score the reference's table shows (their warning
applies here too: scores are for comparing brokers under this harness,
not absolute message rates).

Usage: python benchmarks/e2e_broker.py [--clients 2] [--messages 10000]
The broker runs in-process (loopback TCP) like the reference's
benchmark target; a separate-process broker can be pointed at with
--host/--port.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time


async def run_client(i: int, host: str, port: int, messages: int,
                     payload: bytes, results: list, raw_drain: bool,
                     qos: int = 0):
    from maxmq_tpu.mqtt_client import MQTTClient

    c = MQTTClient(client_id=f"stress-{i}")
    await c.connect(host, port)
    topic = f"stress/{i}/topic"
    await c.subscribe((topic, qos))

    t0 = time.perf_counter()
    if qos == 0:
        for n in range(messages):
            await c.publish(topic, payload)
    else:
        # windowed inflight (mqtt-stresser keeps many unacked publishes
        # outstanding; awaiting each ack would measure the RTT instead)
        window = 64
        for base in range(0, messages, window):
            n = min(window, messages - base)
            await asyncio.gather(
                *(c.publish(topic, payload, qos=qos) for _ in range(n)))
    pub_dt = time.perf_counter() - t0

    # At qos>0 ack-gated publishing fully overlaps delivery, so a timer
    # started after the publish loop would only measure queue-popping;
    # time receipt from publish start instead (what a real stresser
    # reports).
    t0 = t0 if qos else time.perf_counter()
    if raw_drain:
        # count PUBLISH frames straight off the socket: measures BROKER
        # delivery capacity, not this python client's per-message decode
        reader = c.reader
        buf = bytearray(await c.pause_reading())
        got = c.messages.qsize()        # parsed before the pause
        while got < messages:
            got += _count_publish_frames(buf)
            if got >= messages:
                break
            chunk = await asyncio.wait_for(reader.read(1 << 16), 30)
            if not chunk:
                break
            buf.extend(chunk)
    else:
        got = 0
        while got < messages:
            await c.next_message(timeout=30)
            got += 1
    recv_dt = time.perf_counter() - t0
    try:
        await c.disconnect()
    except Exception:
        pass
    results.append((messages / pub_dt, messages / recv_dt))


def _count_publish_frames(buf: bytearray) -> int:
    """Consume complete frames from ``buf``, returning the PUBLISH count
    (frames without per-message Packet.decode — the codec's own framer)."""
    from maxmq_tpu.protocol.packets import parse_stream

    return sum(1 for fh, _body in parse_stream(buf) if fh.type == 3)


async def run_fanout(host: str, port: int, subscribers: int,
                     messages: int, payload: bytes) -> dict:
    """One publisher, N subscribers on one wildcard filter: the
    delivery-amplification scenario the batch fan-out path is built for
    (1 publish -> N deliveries; the broker encodes the QoS0 wire once)."""
    from maxmq_tpu.mqtt_client import MQTTClient

    subs = []
    for i in range(subscribers):
        c = MQTTClient(client_id=f"fan-sub-{i}")
        await c.connect(host, port)
        await c.subscribe(("fan/#", 0))
        subs.append(c)
    pub = MQTTClient(client_id="fan-pub")
    await pub.connect(host, port)

    async def drain(c):
        for _ in range(messages):
            await c.next_message(timeout=60)

    t0 = time.perf_counter()
    tasks = [asyncio.ensure_future(drain(c)) for c in subs]
    for _ in range(messages):
        await pub.publish("fan/x", payload)
    await asyncio.gather(*tasks)
    dt = time.perf_counter() - t0
    for c in subs + [pub]:
        await c.disconnect()
    delivered = subscribers * messages
    return {"deliveries": delivered,
            "deliveries_per_sec": round(delivered / dt, 1),
            "wall_s": round(dt, 2)}


async def run_matchbench(host: str, port: int, messages: int,
                         real_subs: int, publishers: int) -> dict:
    """The integrated-matcher scenario (VERDICT r2 #3): a broker whose
    topic index also holds a large synthetic wildcard corpus, R real
    subscribers, P publishers. Every publish pays a full corpus match
    (trie walk or batched device match) before fan-out; deliveries and
    publish->deliver latency are measured at the real clients."""
    import random
    import struct

    from maxmq_tpu.mqtt_client import MQTTClient

    # publish topics live in the synthetic corpus's OWN alphabet (the
    # bench.build_corpus symbol set), with distinct publish topics, so
    # every publish pays a real full-corpus match — a disjoint topic
    # prefix would let the trie prune at the root and measure nothing
    alphabet = [f"{c}{i}" for c in "abcdefgh" for i in range(12)]
    rng = random.Random(17)

    def topic_for(i: int) -> str:
        return "/".join([alphabet[i % len(alphabet)]] + [
            rng.choice(alphabet) for _ in range(rng.randint(2, 6))])

    subs = []
    for i in range(real_subs):
        c = MQTTClient(client_id=f"mb-sub-{i}")
        await c.connect(host, port)
        await c.subscribe((f"{alphabet[i % len(alphabet)]}/#", 0))
        subs.append(c)

    per_pub = messages // publishers
    expect = {i: 0 for i in range(real_subs)}
    for p in range(publishers):
        for n in range(per_pub):
            expect[(p * per_pub + n) % real_subs] += 1

    lats: list[float] = []

    async def drain(i: int, c: MQTTClient):
        for _ in range(expect[i]):
            m = await c.next_message(timeout=120)
            lats.append(time.time() - struct.unpack(
                "d", m.payload[:8])[0])

    async def publish(p: int):
        c = MQTTClient(client_id=f"mb-pub-{p}")
        await c.connect(host, port)
        for n in range(per_pub):
            i = (p * per_pub + n) % real_subs
            await c.publish(topic_for(i), struct.pack("d", time.time()))
        await c.disconnect()

    # warmup: trigger matcher compile/refresh outside the timed window
    warm = MQTTClient(client_id="mb-warm")
    await warm.connect(host, port)
    await warm.subscribe((f"{alphabet[0]}/#", 0))
    for _ in range(3):
        await warm.publish(topic_for(0), b"\0" * 8)
        try:
            await warm.next_message(timeout=60)
        except Exception:
            pass
        await asyncio.sleep(1.0)
    await warm.disconnect()
    # the warmup topics also matched real subscribers (same corpus
    # alphabet — that is the point of the warm publish): flush their
    # queues so the timed drain neither counts warmup deliveries nor
    # unpacks the zero payloads as epoch-sized latencies
    for c in subs:
        while True:
            try:
                await c.next_message(timeout=0.5)
            except asyncio.TimeoutError:
                break

    t0 = time.perf_counter()
    tasks = [asyncio.ensure_future(drain(i, c))
             for i, c in enumerate(subs)]
    await asyncio.gather(*(publish(p) for p in range(publishers)))
    await asyncio.gather(*tasks)
    dt = time.perf_counter() - t0
    for c in subs:
        await c.disconnect()
    lats.sort()
    n = len(lats)
    return {
        "deliveries": n,
        "deliveries_per_sec": round(n / dt, 1),
        "p50_ms": round(lats[n // 2] * 1e3, 2) if n else None,
        "p99_ms": round(lats[(n * 99) // 100] * 1e3, 2) if n else None,
        "wall_s": round(dt, 2),
    }


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--messages", type=int, default=10_000)
    ap.add_argument("--payload", type=int, default=64)
    ap.add_argument("--fanout", type=int, default=0,
                    help="N: run the 1-publisher/N-subscriber fan-out "
                         "scenario instead of mqtt-stresser 1:1")
    ap.add_argument("--qos", type=int, default=0, choices=(0, 1, 2))
    ap.add_argument("--raw-drain", action="store_true",
                    help="count received PUBLISH frames off the raw "
                         "socket (broker capacity, not python-client "
                         "decode rate)")
    ap.add_argument("--host", default=None,
                    help="external broker host (default: in-process)")
    ap.add_argument("--port", type=int, default=1883)
    ap.add_argument("--matchbench", type=int, default=0,
                    help="N: corpus size for the integrated-matcher A/B "
                         "scenario (synthetic wildcard corpus in the "
                         "broker's index; see --matcher)")
    ap.add_argument("--matcher", default="trie",
                    choices=("trie", "sig", "service"),
                    help="matchbench broker engine: CPU trie, the "
                         "batched signature matcher + MicroBatcher, or "
                         "an external chip-owning matcher service "
                         "(spawned automatically)")
    ap.add_argument("--real-subs", type=int, default=16)
    ap.add_argument("--publishers", type=int, default=2)
    ap.add_argument("--workers", type=int, default=0,
                    help="N>1: run the broker as an ADR-005 worker pool "
                         "(SO_REUSEPORT + fan-out bus) instead of one "
                         "process")
    args = ap.parse_args()

    if args.matchbench and args.host is not None:
        ap.error("--matchbench requires the in-process broker (the "
                 "synthetic corpus and matcher are preloaded into the "
                 "spawned process); drop --host")

    broker = None
    host, port = args.host, args.port
    if host is None:
        # broker in its OWN process (as mqtt-stresser measures the
        # reference: client harness and broker do not share a scheduler)
        import subprocess

        preload = ""
        if args.matchbench:
            preload = (
                "    import bench as benchmod\n"
                "    from maxmq_tpu.protocol.packets import Subscription\n"
                f"    filters, _ = benchmod.build_corpus("
                f"{args.matchbench})\n"
                "    for i, f in enumerate(filters):\n"
                "        b.topics.subscribe(f'syn-{i}', "
                "Subscription(filter=f))\n")
            if args.matcher == "sig":
                preload += (
                    "    from maxmq_tpu.matching.sig import SigEngine\n"
                    "    from maxmq_tpu.matching.batcher import "
                    "MicroBatcher\n"
                    "    eng = SigEngine(b.topics)\n"
                    "    eng.emit_intents = True\n"
                    "    eng.warm_buckets(256, background=False)\n"
                    "    b.attach_matcher(MicroBatcher(eng))\n")
            elif args.matcher == "service":
                # attach forwards the preloaded corpus to the service
                # (index walk reseed) over the socket
                sock = os.environ.get("MAXMQ_BENCH_SERVICE_SOCKET",
                                      "/tmp/maxmq-bench-matcher.sock")
                preload += (
                    "    from maxmq_tpu.matching.service import "
                    "attach_matcher_service\n"
                    f"    await attach_matcher_service(b, {sock!r})\n")
        script = (
            "import asyncio, os, sys\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "from maxmq_tpu.broker import Broker, BrokerOptions, "
            "Capabilities, TCPListener\n"
            "from maxmq_tpu.hooks import AllowHook\n"
            "async def main():\n"
            "    b = Broker(BrokerOptions(capabilities=Capabilities("
            "sys_topic_interval=0)))\n"
            "    b.add_hook(AllowHook())\n"
            + preload +
            "    lst = b.add_listener(TCPListener('bench', "
            "'127.0.0.1:0'))\n"
            "    await b.serve()\n"
            "    print(lst._server.sockets[0].getsockname()[1], "
            "flush=True)\n"
            "    await asyncio.Event().wait()\n"
            "asyncio.run(main())\n")
        service_proc = None
        if args.matchbench and args.matcher == "service":
            sock = os.environ.get("MAXMQ_BENCH_SERVICE_SOCKET",
                                  "/tmp/maxmq-bench-matcher.sock")
            try:                      # a stale socket from an unclean
                os.unlink(sock)       # exit would defeat the bind wait
            except OSError:
                pass
            service_proc = subprocess.Popen(
                [sys.executable, "-m", "maxmq_tpu", "matcher-service",
                 "--socket", sock],
                cwd=REPO, stderr=subprocess.DEVNULL)
            for _ in range(100):
                if os.path.exists(sock):
                    break
                await asyncio.sleep(0.1)
            else:
                ap.error(f"matcher service never bound {sock}")
        if args.workers > 1:
            if args.matchbench:
                ap.error("--workers does not combine with --matchbench "
                         "(the corpus preload is single-process)")
            # ADR-005 pool: drive through the real CLI bootstrap
            import tempfile
            import time as _time

            port = 18883 + (os.getpid() % 1000)
            conf = tempfile.NamedTemporaryFile(
                "w", suffix=".conf", delete=False)
            conf.write(f'workers = {args.workers}\n'
                       f'mqtt_tcp_address = "127.0.0.1:{port}"\n'
                       'metrics_enabled = false\n'
                       'matcher = "trie"\n'
                       'mqtt_sys_topic_interval = 0\n')
            conf.close()
            broker = subprocess.Popen(
                [sys.executable, "-m", "maxmq_tpu", "start",
                 "--config", conf.name, "--no-banner"],
                cwd=REPO, env={**os.environ, "PYTHONPATH": REPO})
            host = "127.0.0.1"
            _time.sleep(6.0)          # pool parent + workers boot
        else:
            broker = subprocess.Popen([sys.executable, "-c", script],
                                      stdout=subprocess.PIPE, text=True)
            host = "127.0.0.1"
            port = int(broker.stdout.readline())

    payload = bytes(args.payload)
    if args.matchbench:
        mb = await run_matchbench(host, port, args.messages,
                                  args.real_subs, args.publishers)
        if broker is not None:
            broker.terminate()
            broker.wait(timeout=10)
        if service_proc is not None:
            service_proc.terminate()
            service_proc.wait(timeout=10)
        sent = (args.messages // args.publishers) * args.publishers
        print(json.dumps({
            "metric": "e2e_broker_matchbench_deliveries_per_sec",
            "corpus_subs": args.matchbench, "matcher": args.matcher,
            "messages": sent, "real_subs": args.real_subs,
            "publishers": args.publishers, **mb}))
        return
    if args.fanout:
        fan = await run_fanout(host, port, args.fanout,
                               args.messages, payload)
        if broker is not None:
            broker.terminate()
            broker.wait(timeout=10)
        print(json.dumps({"metric": "e2e_broker_fanout_deliveries_per_sec",
                          "subscribers": args.fanout,
                          "messages": args.messages, **fan}))
        return

    results: list[tuple[float, float]] = []
    t0 = time.perf_counter()
    await asyncio.gather(*(run_client(i, host, port, args.messages,
                                      payload, results, args.raw_drain,
                                      args.qos)
                           for i in range(args.clients)))
    wall = time.perf_counter() - t0
    if broker is not None:
        broker.terminate()
        broker.wait(timeout=10)

    pub = sorted(r[0] for r in results)
    recv = sorted(r[1] for r in results)
    out = {
        "metric": "e2e_broker_msgs_per_sec",
        "qos": args.qos,
        "clients": args.clients, "messages": args.messages,
        "payload_bytes": args.payload,
        "publish_median_per_client": round(statistics.median(pub), 1),
        "receive_median_per_client": round(statistics.median(recv), 1),
        "publish_aggregate": round(sum(pub), 1),
        "receive_aggregate": round(sum(recv), 1),
        "total_msgs": args.clients * args.messages,
        "wall_s": round(wall, 2),
        "reference_mochi_2_clients": {"publish_median": 125_456,
                                      "receive_median": 313_186,
                                      "hardware": "Apple M2 (README)"},
    }
    print(json.dumps(out))


REPO = __file__.rsplit("/", 2)[0]

if __name__ == "__main__":
    sys.path.insert(0, REPO)
    asyncio.run(main())
