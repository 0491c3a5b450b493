"""Who writes a socket (ADR 019): the flush pass hands an idle writer's
burst to the native sender thread (``native/maxmq_sender.cpp``,
``maxmq_tpu/broker/sender.py``), which writes it without the
interpreter; a short write is handed back to the transport.

The invariant is the transport path's: a peer reads the same bytes in
the same order whoever wrote them, the FIN after the last of them, and
one socket's failure is that socket's alone. The rig drives ``Client``s
of an unserved broker over real sockets (socketpairs and loopback TCP)
whose peers the test reads, so each case can pause a reader, shrink a
send buffer or reset a peer; the broker cases run a served one.
"""

import asyncio
import socket
import struct
import time

import pytest

from test_broker_system import connect, running_broker

from maxmq_tpu import native
from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities
from maxmq_tpu.broker.client import Client
from maxmq_tpu.broker.listeners import MockListener
from maxmq_tpu.broker.sender import SocketSender
from maxmq_tpu.protocol.codec import FixedHeader
from maxmq_tpu.protocol.codec import PacketType as PT
from maxmq_tpu.protocol.packets import Packet


@pytest.fixture
def sender_mod():
    mod = native.sender_module()
    if mod is None:
        pytest.skip("maxmq_sender extension not built (no compiler?)")
    return mod


class Rig:
    """An unserved broker whose flush pass hands bursts to a sender on
    the running loop, as ``Broker.serve`` wires them."""

    def __init__(self) -> None:
        self.broker = Broker(BrokerOptions(
            capabilities=Capabilities(sys_topic_interval=0)))
        self.sender = SocketSender.start(asyncio.get_running_loop())
        assert self.sender is not None
        self.broker.sender = self.sender
        self.broker.flush_sched.sender = self.sender
        self.clients: list = []
        self.peers: list = []

    async def client(self, cid: str, sndbuf: int = 0, tcp: bool = False):
        """A started Client on one end of a socket, and the other end."""
        if tcp:
            lsn = socket.create_server(("127.0.0.1", 0))
            mine = socket.create_connection(lsn.getsockname())
            peer, _ = lsn.accept()
            lsn.close()
        else:
            mine, peer = socket.socketpair()
        if sndbuf:
            mine.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
        reader, writer = await asyncio.open_connection(sock=mine)
        cl = Client(self.broker, reader, writer)
        cl.id = cid
        cl.start()
        peer.setblocking(False)
        self.clients.append(cl)
        self.peers.append(peer)
        await settle()                   # the writer task parks
        return cl, peer

    async def close(self) -> None:
        for cl in self.clients:
            await cl.stop()
        for peer in self.peers:
            peer.close()
        self.sender.close()
        self.broker.hooks.stop_all()


async def read_n(peer, n: int, timeout: float = 10.0) -> bytes:
    loop = asyncio.get_running_loop()
    out = bytearray()
    deadline = time.monotonic() + timeout
    while len(out) < n:
        left = deadline - time.monotonic()
        assert left > 0, f"read {len(out)} of {n} bytes"
        chunk = await asyncio.wait_for(loop.sock_recv(peer, 1 << 16), left)
        if not chunk:
            break
        out += chunk
    return bytes(out)


async def read_to_eof(peer, timeout: float = 10.0) -> bytes:
    loop = asyncio.get_running_loop()
    out = bytearray()
    deadline = time.monotonic() + timeout
    while True:
        left = deadline - time.monotonic()
        assert left > 0, f"no EOF after {len(out)} bytes"
        chunk = await asyncio.wait_for(loop.sock_recv(peer, 1 << 16), left)
        if not chunk:
            return bytes(out)
        out += chunk


async def until(predicate, timeout: float = 5.0) -> None:
    deadline = time.monotonic() + timeout
    while not predicate():
        assert time.monotonic() < deadline, "condition not reached"
        await asyncio.sleep(0.005)


async def settle(n: int = 6) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


def publish_wire(i: int, size: int) -> bytes:
    """A QoS 1 PUBLISH frame (never shed by a budget), ``size`` bytes of
    payload that name ``i``."""
    topic = b"t/%d" % i
    body = struct.pack(">H", len(topic)) + topic + struct.pack(">H", i % 65535 + 1)
    body += (b"%08d" % i * (size // 8 + 1))[:size]
    n, head = len(body), bytearray([0x32])
    while True:
        b, n = n & 0x7F, n >> 7
        head.append(b | (0x80 if n else 0))
        if not n:
            return bytes(head) + body


def publish_packet(i: int, size: int) -> Packet:
    return Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=1),
                  topic=f"p/{i}", packet_id=i % 65535 + 1,
                  payload=b"p" * size)


class Stream:
    """What a client's peer must read: queued items in queue order, and
    each ``send_now`` after the items handed over before it (which is
    the queue's ``removed`` count at its call, on either path)."""

    def __init__(self, cl: Client) -> None:
        self.cl = cl
        self.items: list[bytes] = []
        self.now: list[tuple[int, bytes]] = []

    def queue(self, i: int, kind: int, size: int = 0) -> None:
        cl, size = self.cl, size or 40 + (i * 997) % 3000
        if kind == 0:
            wire = publish_wire(i, size)
            assert cl.send_wire(wire)
        elif kind == 1:                   # a template-style sequence
            wire = publish_wire(i, size)
            bufs = (wire[:3], wire[3:20], wire[20:])
            assert cl.send_buffers(bufs, len(wire))
        else:                             # a Packet item inside a burst
            pkt = publish_packet(i, size)
            wire = pkt.encode()
            assert cl.send(pkt)
        self.items.append(wire)

    def send_now(self) -> None:
        pkt = Packet(fixed=FixedHeader(type=PT.PINGRESP))
        self.now.append((self.cl.outbound.removed, pkt.encode()))
        self.cl.send_now(pkt)

    def expected(self) -> bytes:
        out, at = [], 0
        for pos, wire in self.now:
            out.extend(self.items[at:pos])
            out.append(wire)
            at = pos
        out.extend(self.items[at:])
        return b"".join(out)


# -------------------------------------------------------------------


async def test_order_holds_across_spills_send_now_and_packets(sender_mod):
    """A tiny send buffer and a reader that pauses make the sender's
    writes come up short: the rest goes back to the transport, the
    writer task takes the client over, and the pass hands over again
    once both are empty. Whatever mix of wire items, Packet items and
    ``send_now`` rode which path, the peer reads one stream in order."""
    rig = Rig()
    try:
        cl, peer = await rig.client("order", sndbuf=4096)
        st = Stream(cl)
        st.queue(0, 0, size=200_000)          # more than the socket takes
        i, got = 1, bytearray()
        loop = asyncio.get_running_loop()

        async def reader():
            while True:
                got.extend(await loop.sock_recv(peer, 1 << 16))

        reading = None
        for rnd in range(160):
            if rnd == 80:                     # the peer starts reading
                reading = asyncio.ensure_future(reader())
            for _ in range(1 + rnd % 4):
                st.queue(i, i % 3)
                i += 1
            if rnd % 7 == 3:
                st.send_now()
            await settle(2 + rnd % 3)
            if rnd % 20 == 10:
                await asyncio.sleep(0.02)
        want = st.expected()
        deadline = time.monotonic() + 10
        while len(got) < len(want) and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        reading.cancel()
        assert bytes(got) == want
        stats = rig.sender.stats()
        assert stats["bursts"] > 0
        assert stats["spills"] > 0, "no short write was forced"
        assert rig.broker.flush_sched.woken["backpressure"] > 0
        assert rig.broker.flush_sched.direct > 0
    finally:
        await rig.close()


async def test_direct_bursts_go_to_the_sender_and_counters_move(sender_mod):
    """A reading peer: every burst the pass writes is the sender's,
    none is handed back, and the thread was woken by a pass."""
    rig = Rig()
    try:
        cl, peer = await rig.client("fast", tcp=True)
        st = Stream(cl)
        for i in range(50):
            st.queue(i, i % 2)
            await settle()
            await asyncio.sleep(0.002)
        want = st.expected()
        assert await read_n(peer, len(want)) == want
        sched = rig.broker.flush_sched
        # the thread counts a send once it returns, after the peer may
        # have read it
        await until(lambda: rig.sender.stats()["bursts"] == sched.direct)
        stats = rig.sender.stats()
        assert sched.direct > 0
        assert stats["spills"] == stats["errors"] == 0
        assert stats["wakes"] >= 1 and stats["busy_seconds"] > 0
    finally:
        await rig.close()


async def test_one_burst_held_while_the_task_waits_for_fd_idle(sender_mod):
    """While the sender holds a burst for a socket the pass hands it
    nothing more: the writer task is woken and waits for "fd idle",
    then writes through the transport, behind the sender's bytes."""
    rig = Rig()
    try:
        cl, peer = await rig.client("one")
        await asyncio.sleep(0.05)            # the thread is asleep
        handle, sched = cl._channel, rig.broker.flush_sched
        sched.sender = None                  # no kick: the burst stays held
        st = Stream(cl)
        submits = []
        sink = cl._sink

        def counting(bufs):
            submits.append(rig.sender.idle(handle))
            return sink(bufs)
        cl._sink = counting
        st.queue(0, 0)
        await settle()
        assert submits == [True] and not rig.sender.idle(handle)
        st.queue(1, 0)
        st.queue(2, 1)
        await settle()
        assert submits == [True], "a second burst went to a busy fd"
        assert sched.woken["backpressure"] == 1
        assert cl.outbound.qsize() == 2       # accounted, not handed over
        assert handle in rig.sender._waiters  # the task waits for idle
        sched.sender = rig.sender
        rig.sender.kick()
        want = st.expected()
        assert await read_n(peer, len(want)) == want
        await settle()
        assert cl.outbound.qsize() == 0 and rig.sender.idle(handle)
    finally:
        await rig.close()


async def test_a_cancelled_idle_wait_leaves_the_next_one_working(
        sender_mod):
    """A writer cancelled while it waits for "fd idle" leaves nothing
    behind that ends the next wait early."""
    rig = Rig()
    try:
        a, b = socket.socketpair()
        b.setblocking(False)
        sender, core = rig.sender, rig.sender._core
        await asyncio.sleep(0.05)            # the thread is asleep
        h = core.open(a.fileno())
        assert core.submit(h, [b"x"])        # held: nobody kicks
        first = asyncio.ensure_future(sender.wait_idle(h))
        await settle()
        first.cancel()
        await settle()
        second = asyncio.ensure_future(sender.wait_idle(h))
        await settle()
        assert not second.done()
        sender.kick()
        await asyncio.wait_for(second, 5)
        assert await read_n(b, 1) == b"x"
        sender.forget(h)
        a.close()
        b.close()
    finally:
        await rig.close()


async def test_fin_only_after_the_senders_last_byte(sender_mod):
    """``stop`` with a burst still in the sender: the peer reads every
    byte, then EOF. And at the sender's own level: bytes submitted,
    the handle forgotten and the transport's socket closed before the
    thread ever ran still arrive ahead of the FIN (the dup holds it)."""
    rig = Rig()
    try:
        cl, peer = await rig.client("fin", tcp=True)
        st = Stream(cl)
        for i in range(20):
            st.queue(i, i % 3)
        await settle(2)
        reading = asyncio.ensure_future(read_to_eof(peer))
        await cl.stop()
        assert await reading == st.expected()

        a, b = socket.socketpair()
        b.setblocking(False)
        core = rig.sender._core
        await asyncio.sleep(0.05)            # the thread is asleep
        h = core.open(a.fileno())
        assert core.submit(h, [b"last ", b"words"])
        core.forget(h)
        a.close()                            # the socket's own fd
        core.kick()
        assert await read_to_eof(b) == b"last words"
        b.close()
    finally:
        await rig.close()


async def test_peer_reset_fails_that_client_only(sender_mod):
    """A reset peer: the sender drops that socket's bytes, the loop
    records the error and ends that writer; the other client's stream
    is whole and its writer lives."""
    rig = Rig()
    try:
        bad, bad_peer = await rig.client("bad", tcp=True)
        good, good_peer = await rig.client("good", tcp=True)
        bad_peer.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
        bad_peer.close()                      # RST
        await asyncio.sleep(0.05)
        st = Stream(good)
        for i in range(30):
            st.queue(i, i % 3)
            assert bad.send_wire(publish_wire(i, 100))
            await settle()
            await asyncio.sleep(0.002)
        want = st.expected()
        assert await read_n(good_peer, len(want)) == want
        deadline = time.monotonic() + 5
        while bad.write_error is None and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        assert bad.write_error is not None
        assert rig.sender.stats()["errors"] >= 1
        await settle()
        assert bad._writer_task.done()
        assert good.write_error is None and not good._writer_task.done()
    finally:
        await rig.close()


# --------------------------- who takes the transport's path, as before


async def test_facades_and_tls_keep_the_transport_path(sender_mod, tmp_path):
    """The sender writes only a plain TCP or Unix socket's selector
    transport: the in-process pipe (``_QueueWriter``), a writer with no
    transport (as ``_WSWriter``) and TLS get no handle, and their bytes
    go through the writer as before."""
    import ssl
    import subprocess

    async with running_broker() as broker:
        assert broker.sender is not None
        mock = broker.add_listener(MockListener("mock"))
        await mock.serve(broker._establish)
        reader, writer = await mock.connect()

        class Facade:
            def write(self, data):
                pass

        assert broker.sender.open(
            type("C", (), {"writer": writer})()) is None
        assert broker.sender.open(
            type("C", (), {"writer": Facade()})()) is None

        key, crt = tmp_path / "k.pem", tmp_path / "c.pem"
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", str(key), "-out", str(crt), "-days", "1",
             "-subj", "/CN=localhost"], check=True, capture_output=True)
        server_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        server_ctx.load_cert_chain(str(crt), str(key))
        from maxmq_tpu.broker import TCPListener
        from maxmq_tpu.mqtt_client import MQTTClient
        lst = broker.add_listener(
            TCPListener("tls1", "127.0.0.1:0", tls=server_ctx))
        await lst.serve(broker._establish)
        port = lst._server.sockets[0].getsockname()[1]
        client_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        client_ctx.check_hostname = False
        client_ctx.verify_mode = ssl.CERT_NONE
        r, w = await asyncio.open_connection("127.0.0.1", port,
                                             ssl=client_ctx)
        tls = MQTTClient(client_id="tls-c")
        await tls.connect(None, None, reader=r, writer=w)
        plain = await connect(broker, "plain")
        for c in (tls, plain):
            await c.subscribe(("s/#", 1))
        assert broker.clients.get("tls-c")._channel is None
        assert broker.clients.get("plain")._channel is not None
        before = broker.sender.stats()["bursts"]
        for i in range(5):
            await plain.publish("s/x", b"%d" % i, qos=1)
        for c in (tls, plain):
            got = [(await c.next_message(timeout=10)).payload
                   for _ in range(5)]
            assert got == [b"%d" % i for i in range(5)]
        assert broker.sender.stats()["bursts"] > before
        for c in (tls, plain):
            await c.disconnect()


async def test_no_native_library_keeps_the_transport_path(monkeypatch):
    """``MAXMQ_NO_NATIVE``: no sender, and a served broker writes every
    socket through its transport exactly as before."""
    monkeypatch.setenv("MAXMQ_NO_NATIVE", "1")
    monkeypatch.delitem(native._extensions, "maxmq_sender", raising=False)
    async with running_broker() as broker:
        assert broker.sender is None and broker.flush_sched.sender is None
        sub = await connect(broker, "sub")
        await sub.subscribe(("n/#", 1))
        pub = await connect(broker, "pub")
        assert broker.clients.get("sub")._sink is None
        for i in range(10):
            await pub.publish("n/x", b"%d" % i, qos=1)
        got = [(await sub.next_message(timeout=10)).payload
               for _ in range(10)]
        assert got == [b"%d" % i for i in range(10)]
        assert broker.flush_sched.direct > 0
        for c in (sub, pub):
            await c.disconnect()


async def test_served_broker_hands_bursts_over_and_closes_the_thread(
        sender_mod):
    """End to end: the CONNACK, the deliveries and the PUBACKs of a
    served broker reach their sockets in order through the sender, and
    ``close`` stops it."""
    async with running_broker() as broker:
        sender = broker.sender
        assert sender is not None and broker.flush_sched.sender is sender
        subs = [await connect(broker, f"s{k}") for k in range(3)]
        for s in subs:
            await s.subscribe(("e/#", 1))
        pub = await connect(broker, "p")
        for i in range(40):
            await pub.publish(f"e/{i % 4}", b"%d" % i, qos=1)
        for s in subs:
            got = [(await s.next_message(timeout=10)).payload
                   for _ in range(40)]
            assert got == [b"%d" % i for i in range(40)]
        assert sender.stats()["bursts"] > 0
        assert sender.stats()["spills"] == sender.stats()["errors"] == 0
        for c in subs + [pub]:
            await c.disconnect()
    assert sender.closed


def test_sender_counters_exported(sender_mod):
    from maxmq_tpu.metrics import Registry, register_broker_metrics

    async def body():
        broker = Broker(BrokerOptions(
            capabilities=Capabilities(sys_topic_interval=0)))
        reg = Registry()
        register_broker_metrics(reg, broker)
        assert "maxmq_broker_sender_bursts_total 0" in reg.expose()
        broker.sender = SocketSender.start(asyncio.get_running_loop())
        a, b = socket.socketpair()
        b.setblocking(False)
        h = broker.sender._core.open(a.fileno())
        broker.sender.submit(h, [b"x"])
        broker.sender.kick()
        assert await asyncio.wait_for(
            asyncio.get_running_loop().sock_recv(b, 1), 5) == b"x"
        await until(lambda: broker.sender.stats()["bursts"] == 1)
        text = reg.expose()
        assert "maxmq_broker_sender_bursts_total 1" in text
        for name in ("spills_total", "errors_total"):
            assert f"maxmq_broker_sender_{name} 0" in text
        assert "maxmq_broker_sender_busy_seconds_total" in text
        assert "maxmq_broker_sender_wakes_total" in text
        broker.sender.close()
        a.close()
        b.close()
        broker.hooks.stop_all()

    asyncio.run(body())
