#!/usr/bin/env python3
"""The load generator: MQTT 3.1.1 clients over TCP, in a process of its
own that imports neither JAX nor the program under test.

``run.py`` starts a few of these and tells each what to do a line at a
time on stdin; each answers a line with one JSON line on stdout. A
generator holds a slice of the cell's publishers and of its live
subscribers. Publishers send open loop (every message has a due time
from a Poisson schedule made from the seed, and is sent when it is due
whether or not the broker keeps up) or closed loop (at most ``in_flight``
messages beyond the oldest QoS 1 PUBLISH not yet PUBACKed). Every payload
opens with ``<publisher>:<seq>:<due_ns>|``: ``time.monotonic_ns()`` is one
clock for every process of the host, so a subscriber in another process
can time a delivery from when the message was due. Subscribers work on
frames: they stamp the arrival of a chunk, cut it into packets, PUBACK
QoS 1 and keep ``(head, arrival_ns, qos)``. What was sent (with what the
plain reference says it should reach) and what arrived go to one pickle
per phase, which ``run.py`` joins and checks after the window.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import pickle
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import arith  # noqa: E402
import generators  # noqa: E402
from reference import Reference  # noqa: E402

FILLER = random.Random(7).randbytes(2048)


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, d = divmod(n, 128)
        out.append(d | (0x80 if n else 0))
        if not n:
            return bytes(out)


def connect_packet(client_id: str, clean: bool) -> bytes:
    cid = client_id.encode()
    # keepalive 3600: these clients send no PINGREQ of their own
    body = (b"\x00\x04MQTT\x04" + bytes([0x02 if clean else 0x00])
            + (3600).to_bytes(2, "big") + len(cid).to_bytes(2, "big") + cid)
    return b"\x10" + varint(len(body)) + body


def publish_packet(topic: bytes, payload: bytes, qos: int, pid: int) -> bytes:
    body = len(topic).to_bytes(2, "big") + topic
    if qos:
        body += pid.to_bytes(2, "big")
    body += payload
    return bytes([0x30 | (qos << 1)]) + varint(len(body)) + body


class Conn(asyncio.Protocol):
    """One MQTT connection, as a publisher or as a subscriber."""

    def __init__(self, client_id: str, clean: bool) -> None:
        self.client_id = client_id
        self.clean = clean
        self.transport = None
        self.buf = bytearray()
        self.connack = asyncio.get_running_loop().create_future()
        self.session_present = False
        self.lost = ""
        self.writable = True
        # subscriber side
        self.got: list = []             # (head, arrival_ns, qos)
        # publisher side
        self.pending: dict = {}         # pid -> seq, QoS 1 not PUBACKed
        self.next_pid = 0
        self.on_room = None             # closed loop: called on PUBACK

    def connection_made(self, transport) -> None:
        self.transport = transport
        transport.write(connect_packet(self.client_id, self.clean))

    def connection_lost(self, exc) -> None:
        self.lost = repr(exc) if exc else "closed by the broker"
        if not self.connack.done():
            self.connack.set_exception(ConnectionError(self.lost))

    def pause_writing(self) -> None:
        self.writable = False

    def resume_writing(self) -> None:
        self.writable = True
        if self.on_room is not None:
            self.on_room()

    def data_received(self, data: bytes) -> None:
        now = time.monotonic_ns()
        buf = self.buf
        buf += data
        pos, n = 0, len(buf)
        acks = bytearray()
        room = False
        while n - pos >= 2:
            b0 = buf[pos]
            i, rem, shift = pos + 1, 0, 0
            while True:
                if i >= n:
                    rem = -1
                    break
                d = buf[i]
                i += 1
                rem |= (d & 0x7F) << shift
                shift += 7
                if d < 0x80:
                    break
            if rem < 0 or i + rem > n:
                break
            ptype = b0 >> 4
            if ptype == 3:
                qos = (b0 >> 1) & 3
                j = i + 2 + ((buf[i] << 8) | buf[i + 1])
                if qos:
                    acks += b"\x40\x02" + buf[j:j + 2]
                    j += 2
                bar = buf.find(b"|", j, i + rem)
                self.got.append((bytes(buf[j:bar]), now, qos | (b0 & 8)))
            elif ptype == 4:
                self.pending.pop((buf[i] << 8) | buf[i + 1], None)
                room = True
            elif ptype == 2:
                self.session_present = bool(buf[i] & 1)
                if buf[i + 1] == 0:
                    self.connack.set_result(True)
                else:
                    self.connack.set_exception(
                        ConnectionError(f"CONNACK {buf[i + 1]}"))
            pos = i + rem
        del buf[:pos]
        if acks:
            self.transport.write(bytes(acks))
        if room and self.on_room is not None:
            self.on_room()

    def publish(self, topic: str, payload: bytes, qos: int, seq: int) -> None:
        pid = 0
        if qos:
            pid = self.next_pid = self.next_pid % 65535 + 1
            self.pending[pid] = seq
        self.transport.write(publish_packet(topic.encode(), payload, qos,
                                            pid))


class Publisher:
    """One publisher's stream of messages, drawn from its own RNG."""

    def __init__(self, index: int, conn: Conn, traffic: dict, seed: int,
                 draw_topic) -> None:
        self.index = index
        self.conn = conn
        self.rng = random.Random(seed * 1000003 + index)
        self.draw_topic = draw_topic
        self.qos1_share = traffic["qos1_share"]
        self.lo, self.hi = traffic["payload_bytes"]
        self.seq = 0
        self.sent: list = []        # (pub, seq, topic, qos, due_ns, sent_ns)

    def draw(self) -> tuple:
        """(topic, qos, payload size) of the next message."""
        rng = self.rng
        return (self.draw_topic(rng),
                1 if rng.random() < self.qos1_share else 0,
                rng.randint(self.lo, self.hi))

    def send(self, msg: tuple, due_ns: int) -> None:
        topic, qos, size = msg
        seq = self.seq
        self.seq = seq + 1
        head = b"%d:%d:%d|" % (self.index, seq, due_ns)
        self.conn.publish(topic, head + FILLER[:max(0, size - len(head))],
                          qos, seq)
        self.sent.append((self.index, seq, topic, qos, due_ns,
                          time.monotonic_ns()))


class Generator:
    def __init__(self, args) -> None:
        self.args = args
        with open(args.traffic) as fh:
            self.traffic = json.load(fh)
        with open(args.config) as fh:
            self.config = json.load(fh)
        live = self.config["live"]
        self.plan, _groups, self.hits = generators.find(
            live["recipe"])(args.seed, **live.get("args", {}))
        self.ref = Reference(self.plan)
        self.pubs: list[Publisher] = []
        self.subs: dict[str, Conn] = {}
        self.phase = 0

    async def connect(self) -> dict:
        loop = asyncio.get_running_loop()
        a = self.args
        draw = generators.topic_source(self.traffic, a.seed, self.hits)
        conns = []
        for p in range(a.index, self.traffic["publishers"], a.of):
            _, conn = await loop.create_connection(
                lambda p=p: Conn(f"load-p{p}", True), "127.0.0.1", a.port)
            self.pubs.append(Publisher(p, conn, self.traffic, a.seed, draw))
            conns.append(conn)
        # the live subscribers are persistent sessions in the store:
        # they come back with clean_start = 0 and SUBSCRIBE nothing
        for k, cid in enumerate(sorted(self.plan)):
            if k % a.of == a.index:
                _, conn = await loop.create_connection(
                    lambda cid=cid: Conn(cid, False), "127.0.0.1", a.port)
                self.subs[cid] = conn
                conns.append(conn)
        await asyncio.wait_for(asyncio.gather(*(c.connack for c in conns)),
                               60)
        return {"publishers": len(self.pubs), "subscribers": len(self.subs),
                "sessions_resumed": sum(c.session_present
                                        for c in self.subs.values())}

    def lost(self) -> list:
        return [f"{c.client_id}: {c.lost}"
                for c in [p.conn for p in self.pubs] + list(self.subs.values())
                if c.lost]

    # -- the two loops ------------------------------------------------------

    async def closed_loop(self, t0: int, t1: int) -> dict:
        in_flight = self.traffic["in_flight"]

        def pump(pub: Publisher) -> None:
            conn = pub.conn
            while conn.writable and not conn.lost:
                now = time.monotonic_ns()
                if now >= t1:
                    conn.on_room = None
                    return
                floor = (next(iter(conn.pending.values())) if conn.pending
                         else pub.seq)
                if pub.seq - floor >= in_flight:
                    return
                pub.send(pub.draw(), now)

        await asyncio.sleep(max(0.0, (t0 - time.monotonic_ns()) / 1e9))
        for pub in self.pubs:
            pub.conn.on_room = lambda pub=pub: pump(pub)
            pump(pub)
        await asyncio.sleep(max(0.0, (t1 - time.monotonic_ns()) / 1e9))
        for pub in self.pubs:
            pub.conn.on_room = None
        return {}

    async def open_loop(self, t0: int, t1: int) -> dict:
        """Every publisher's own Poisson stream at rate / publishers,
        merged into one list by due time and made before the start."""
        rate = self.traffic["rate"] / self.traffic["publishers"]
        plan = []
        for pub in self.pubs:
            offsets = arith.poisson_schedule(
                rate, (t1 - t0) / 1e9,
                self.args.seed * 7919 + pub.index * 31 + self.phase)
            plan += [(t0 + off, pub.index, pub, pub.draw()) for off in offsets]
        plan.sort(key=lambda m: m[:2])
        late = []
        for due, _i, pub, msg in plan:
            while True:
                wait = due - time.monotonic_ns()
                if wait <= 0:
                    break
                # the loop's timers are a millisecond coarse: sleep to
                # within 1.2 ms, then yield until the time has come
                await asyncio.sleep((wait - 1_200_000) / 1e9
                                    if wait > 1_500_000 else 0)
            pub.send(msg, due)
            late.append(pub.sent[-1][5] - due)
        return {"gen_late_p50_ms": arith.percentile(late, 50) / 1e6,
                "gen_late_p99_ms": arith.percentile(late, 99) / 1e6,
                "gen_late_max_ms": max(late) / 1e6} if late else {}

    # -- one phase ------------------------------------------------------------

    async def run_phase(self, t0: int, seconds: float, out: str) -> dict:
        """Send from ``t0`` for ``seconds``, wait for the PUBACKs and for
        the deliveries to stop coming, then write what was sent (with the
        receivers the reference expects) and what arrived to ``out``."""
        self.phase += 1
        t1 = t0 + int(seconds * 1e9)
        for pub in self.pubs:
            pub.sent = []
        for conn in self.subs.values():
            conn.got = []
        cpu0, wall0 = time.process_time(), time.monotonic()
        loop_fn = (self.open_loop if self.traffic["loop"] == "open"
                   else self.closed_loop)
        stats = await loop_fn(t0, t1)
        stats["gen_cpu_share"] = ((time.process_time() - cpu0)
                                  / (time.monotonic() - wall0))
        # drain: every PUBACK in, and no delivery for `quiet` seconds
        grace, quiet = self.traffic["drain_grace_s"], 1.0
        end = time.monotonic() + grace
        seen, since = -1, time.monotonic()
        while time.monotonic() < end:
            n = sum(len(c.got) for c in self.subs.values())
            if n != seen:
                seen, since = n, time.monotonic()
            if (time.monotonic() - since >= quiet
                    and not any(p.conn.pending for p in self.pubs)):
                break
            await asyncio.sleep(0.05)
        sent = []
        for pub in self.pubs:
            for rec in pub.sent:
                plain, shared = self.ref.receivers(rec[2])
                sent.append(rec + (plain, shared))
        with open(out, "wb") as fh:
            pickle.dump({"sent": sent,
                         "got": {cid: c.got for cid, c in self.subs.items()},
                         "unacked": {p.index: sorted(p.conn.pending.values())
                                     for p in self.pubs if p.conn.pending}},
                        fh, protocol=pickle.HIGHEST_PROTOCOL)
        stats.update(sent=len(sent),
                     received=sum(len(c.got) for c in self.subs.values()),
                     failures=[f"lost connection: {x}" for x in self.lost()])
        return stats

    async def main(self) -> None:
        loop = asyncio.get_running_loop()
        try:
            reply = await self.connect()
        except Exception as exc:
            reply = {"failures": [f"connect: {exc!r}"]}
        print(json.dumps(reply), flush=True)
        while True:
            line = (await loop.run_in_executor(None, sys.stdin.readline))
            cmd = line.split()
            if not cmd or cmd[0] == "quit":
                break
            if cmd[0] == "phase":   # phase <t0_ns> <seconds> <out> [rate]
                if len(cmd) > 4:        # the sweep's rate for this step
                    self.traffic["rate"] = float(cmd[4])
                try:
                    reply = await self.run_phase(int(cmd[1]), float(cmd[2]),
                                                 cmd[3])
                except Exception as exc:
                    import traceback
                    traceback.print_exc()
                    reply = {"failures": [f"phase raised {exc!r}"]}
            else:
                reply = {"failures": [f"unknown command {line!r}"]}
            print(json.dumps(reply), flush=True)
        for conn in [p.conn for p in self.pubs] + list(self.subs.values()):
            if not conn.lost:
                conn.transport.write(b"\xe0\x00")
                conn.transport.close()
        await asyncio.sleep(0.1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--index", type=int, required=True)
    ap.add_argument("--of", type=int, required=True)
    # a collection over a few hundred thousand delivery records would
    # stall this process's clients for tens of milliseconds inside the
    # window; the records hold no cycles, and the process is short-lived
    gc.disable()
    asyncio.run(Generator(ap.parse_args()).main())
    return 0


if __name__ == "__main__":
    sys.exit(main())
