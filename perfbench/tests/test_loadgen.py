"""The generator's own MQTT framing: what it writes is what it reads,
however the bytes are cut into chunks."""

import asyncio
import random

import loadgen


class Wire:
    def __init__(self):
        self.out = bytearray()

    def write(self, data):
        self.out += data


def run(coro):
    return asyncio.run(coro)


def test_varint_edges():
    assert loadgen.varint(0) == b"\x00"
    assert loadgen.varint(127) == b"\x7f"
    assert loadgen.varint(128) == b"\x80\x01"
    assert loadgen.varint(16_383) == b"\xff\x7f"
    assert loadgen.varint(16_384) == b"\x80\x80\x01"
    assert loadgen.varint(268_435_455) == b"\xff\xff\xff\x7f"


def test_connect_packet():
    pkt = loadgen.connect_packet("live-s1", clean=False)
    assert pkt[0] == 0x10 and pkt[1] == len(pkt) - 2
    assert pkt[2:9] == b"\x00\x04MQTT\x04" and pkt[9] == 0x00
    assert loadgen.connect_packet("p", clean=True)[9] == 0x02
    assert pkt.endswith(b"\x00\x07live-s1")


def test_frames_survive_any_chunking():
    async def body():
        rng = random.Random(3)
        stream = bytearray(b"\x20\x02\x01\x00")     # CONNACK, session present
        want = []
        for seq in range(200):
            qos = seq % 2
            size = rng.choice((0, 3, 100, 127, 128, 300, 20_000))
            head = b"%d:%d:%d" % (7, seq, 123_456_789 + seq)
            topic = ("a/" * rng.randint(1, 40) + "z").encode()
            stream += loadgen.publish_packet(
                topic, head + b"|" + bytes(size), qos, 1 + seq)
            want.append((head, qos))
        stream += b"\x40\x02\x00\x09"               # a PUBACK for pid 9
        conn = loadgen.Conn("c", clean=False)
        conn.transport = Wire()
        conn.pending[9] = 99
        rooms = []
        conn.on_room = lambda: rooms.append(1)
        pos = 0
        while pos < len(stream):
            n = rng.choice((1, 2, 3, 5, 64, 1_000, 70_000))
            conn.data_received(bytes(stream[pos:pos + n]))
            pos += n
        assert await conn.connack and conn.session_present
        assert [(h, q) for h, _t, q in conn.got] == want
        assert all(t > 0 for _h, t, _q in conn.got)
        # one PUBACK per QoS 1 delivery, with its packet id, in order
        acks = bytes(conn.transport.out)
        assert acks == b"".join(b"\x40\x02" + (1 + s).to_bytes(2, "big")
                                for s in range(200) if s % 2)
        assert conn.pending == {} and rooms == [1]
    run(body())


def test_publisher_payload_head_and_sizes():
    async def body():
        conn = loadgen.Conn("p", clean=True)
        conn.transport = Wire()
        traffic = {"qos1_share": 0.5, "payload_bytes": [64, 512]}
        pub = loadgen.Publisher(3, conn, traffic, seed=3_000_000_011,
                                draw_topic=lambda rng: "t/x")
        for _ in range(300):
            pub.send(pub.draw(), due_ns=42)
        again = loadgen.Publisher(3, loadgen.Conn("p", True), traffic,
                                  seed=3_000_000_011,
                                  draw_topic=lambda rng: "t/x")
        assert [again.draw()[1] for _ in range(300)] == \
            [r[3] for r in pub.sent]        # the same seed, the same stream
        sub = loadgen.Conn("s", clean=False)
        sub.transport = Wire()
        sub.data_received(bytes(conn.transport.out))
        assert [h for h, _t, _q in sub.got] == \
            [b"3:%d:42" % k for k in range(300)]
        qos1 = sum(q for _h, _t, q in sub.got)
        assert 110 < qos1 < 190
        assert sorted(conn.pending.values()) == \
            [r[1] for r in pub.sent if r[3]]
    run(body())
