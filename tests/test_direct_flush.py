"""The flush pass writes (ADR 019): an idle writer's backlog goes from
the pass to the socket itself, and the writer task is woken only for
what needs back-pressure.

The one invariant: what a subscriber's socket receives is the same
bytes in the same order whoever wrote a burst. The unit cases drive a
``Client`` over a recording writer whose transport says how many bytes
it still holds, so each case chooses its path (direct, back-pressured
task, facade) and compares streams; the end-to-end cases run a broker
with a matcher attached, so the publish pipeline's consumer calls
``flush_now`` where it runs dry.
"""

import asyncio
import time

import pytest

from test_broker_fixes import _TrieMatcher
from test_broker_system import connect, running_broker

from maxmq_tpu import faults
from maxmq_tpu.broker import Broker, BrokerOptions, Capabilities
from maxmq_tpu.broker.client import Client
from maxmq_tpu.broker.sender import SocketSender
from maxmq_tpu.protocol.codec import FixedHeader
from maxmq_tpu.protocol.codec import PacketType as PT
from maxmq_tpu.protocol.packets import Packet


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


async def poll(predicate, timeout: float = 5.0, what: str = ""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"condition not reached in {timeout}s: {what}")


class _Transport:
    """What the direct path asks of a transport: the bytes it holds."""

    def __init__(self) -> None:
        self.held = 0

    def get_write_buffer_size(self) -> int:
        return self.held

    def set_write_buffer_limits(self, high=None, low=None) -> None:
        pass


class RecordingWriter:
    """A StreamWriter that keeps what it was handed, call by call."""

    def __init__(self, transport=True) -> None:
        if transport:
            self.transport = _Transport()
        self.calls: list[bytes] = []
        self.raises: BaseException | None = None
        self.gate: asyncio.Event | None = None   # set = drain() returns

    def _take(self, data: bytes) -> None:
        if self.raises is not None:
            raise self.raises
        self.calls.append(data)

    def write(self, data) -> None:
        self._take(bytes(data))

    def writelines(self, bufs) -> None:
        self._take(b"".join(bufs))

    async def drain(self) -> None:
        if self.gate is not None:
            await self.gate.wait()

    def close(self) -> None:
        pass

    def get_extra_info(self, name, default=None):
        return default

    @property
    def stream(self) -> bytes:
        return b"".join(self.calls)


class FacadeWriter(RecordingWriter):
    """write() alone: no transport to ask, no writelines (as _WSWriter)."""

    writelines = None

    def __init__(self) -> None:
        super().__init__(transport=False)


def _broker() -> Broker:
    return Broker(BrokerOptions(
        capabilities=Capabilities(sys_topic_interval=0)))


def _client(broker, writer, cid: str) -> Client:
    cl = Client(broker, None, writer)
    cl.id = cid
    cl.start()
    return cl


async def _settle() -> None:
    """Let the writer tasks park, and a scheduled pass run."""
    for _ in range(4):
        await asyncio.sleep(0)


# -- items of every kind the queue carries ------------------------------


def _qos0_wire(n: int) -> bytes:
    body = b"\x00\x03a/b" + b"%04d" % n
    return bytes([0x30, len(body)]) + body


def _qos0_template(n: int) -> tuple:
    """A buffer sequence as PublishTemplate.patch builds it: a fresh
    head and the shared segments."""
    payload = b"tmpl%04d" % n
    topic = b"\x00\x03a/b"
    return (bytes([0x30, len(topic) + len(payload)]), topic, payload)


def _qos1_packet(n: int) -> Packet:
    return Packet(fixed=FixedHeader(type=PT.PUBLISH, qos=1),
                  protocol_version=4, topic="a/b", packet_id=n + 1,
                  payload=b"pkt%04d" % n)


def _ack(n: int) -> bytes:
    return bytes((PT.PUBACK << 4, 2, n >> 8, n & 0xFF))


_KINDS = {
    "bytes": lambda n: [_qos0_wire(n)],
    "tuple": lambda n: [_qos0_template(n)],
    "packet": lambda n: [_qos1_packet(n)],
    "interleaved": lambda n: [_qos0_template(n), _qos1_packet(n), _ack(n),
                              _qos0_wire(n)],
}


def _send(cl: Client, item) -> None:
    if type(item) is bytes:
        assert cl.send_wire(item)
    elif type(item) is tuple:
        assert cl.send_buffers(item, sum(len(b) for b in item))
    else:
        assert cl.send(item)


def _wire_of(item) -> bytes:
    if type(item) is bytes:
        return item
    if type(item) is tuple:
        return b"".join(item)
    return item.encode()


@pytest.mark.parametrize("kind", sorted(_KINDS))
async def test_same_bytes_whoever_writes(kind):
    """The stream is identical, item for item and in order, whether the
    pass wrote the bursts, the task did (a transport that holds bytes),
    or the writer is a facade with no transport to ask."""
    broker = _broker()
    sched = broker.flush_sched
    direct, pushed, facade = (RecordingWriter(), RecordingWriter(),
                              FacadeWriter())
    pushed.transport.held = 1
    clients = [_client(broker, w, f"c{i}")
               for i, w in enumerate((direct, pushed, facade))]
    await _settle()
    want = b""
    for burst in range(3):
        items = [it for n in range(burst * 4, burst * 4 + 4)
                 for it in _KINDS[kind](n)]
        want += b"".join(_wire_of(it) for it in items)
        for cl in clients:
            for it in items:
                _send(cl, it)
        await _settle()
    assert direct.stream == want
    assert pushed.stream == want
    assert facade.stream == want
    assert sched.direct == 3
    assert sched.woken["backpressure"] == 3 and sched.woken["facade"] == 3
    assert all(cl.outbound.qsize() == 0 and cl.outbound.bytes == 0
               for cl in clients)
    if kind in ("bytes", "tuple"):
        # coalescing kept: one writelines a burst, whoever wrote it
        assert len(direct.calls) == len(pushed.calls) == 3
    for cl in clients:
        await cl.stop()


async def test_direct_write_happens_inside_the_pass():
    """flush_now writes in the caller's step; the call_soon already
    scheduled then finds nothing, and the task never woke."""
    broker = _broker()
    sched = broker.flush_sched
    w = RecordingWriter()
    cl = _client(broker, w, "now")
    await _settle()
    getter = cl.outbound._getter
    for n in range(5):
        _send(cl, _qos0_wire(n))
    assert w.calls == [] and sched.deferred == 1 and sched.coalesced == 4
    sched.flush_now()
    assert w.calls == [b"".join(_qos0_wire(n) for n in range(5))]
    assert (sched.flushes, sched.direct) == (1, 1)
    await _settle()                     # the call_soon pass: nothing left
    assert (sched.flushes, sched.direct) == (1, 1)
    assert cl.outbound._getter is getter and not getter.done()
    await cl.stop()


@pytest.mark.parametrize("paused", [False, True],
                         ids=["holds_bytes", "paused_in_drain"])
async def test_busy_transport_goes_to_the_task_and_order_holds(paused):
    """A transport that still holds bytes sends the backlog to the task.
    While the task waits in drain() nothing is written past it, and when
    the direct path resumes the stream is still in order."""
    broker = _broker()
    sched = broker.flush_sched
    w = RecordingWriter()
    cl = _client(broker, w, "bp")
    await _settle()
    w.transport.held = 4096
    if paused:
        w.gate = asyncio.Event()
    items = [_qos0_wire(n) for n in range(12)]
    for it in items[:4]:
        _send(cl, it)
    await _settle()
    assert sched.direct == 0 and sched.woken["backpressure"] == 1
    assert w.stream == b"".join(items[:4])      # the task wrote them
    for it in items[4:8]:
        _send(cl, it)
    await _settle()
    if paused:
        # the task is in drain(): the queue keeps the backlog, accounted
        assert w.stream == b"".join(items[:4])
        assert cl.outbound.qsize() == 4
        assert cl.outbound.bytes == sum(len(i) for i in items[4:8])
        assert sched.deferred == 1              # no getter to park for
        w.gate.set()
        await _settle()
    assert w.stream == b"".join(items[:8])
    w.transport.held = 0                        # the consumer caught up
    for it in items[8:]:
        _send(cl, it)
    await _settle()
    assert sched.direct == 1
    assert w.stream == b"".join(items)
    await cl.stop()


async def test_burst_cap_hands_the_rest_to_the_task():
    """One burst at most goes direct: past BURST_BYTES the rest stays in
    the accounted queue for the task (ADR 012)."""
    broker = _broker()
    sched = broker.flush_sched
    w = RecordingWriter()
    w.gate = asyncio.Event()
    cl = _client(broker, w, "cap")
    await _settle()
    wire = bytes([0x30, 0x7F]) + b"x" * 30000
    for _ in range(7):
        assert cl.send_wire(wire)
    sched.flush_now()
    assert len(w.stream) == 3 * len(wire)       # 90,006 >= 65,536
    assert cl.outbound.bytes == 4 * len(wire)
    assert broker.overload.queued_bytes == cl.outbound.bytes
    assert sched.direct == 0 and sched.woken["backpressure"] == 1
    await _settle()                             # the task: one more burst
    assert len(w.stream) == 6 * len(wire)
    assert cl.outbound.bytes == len(wire)       # parked in drain()
    w.gate.set()
    await _settle()
    assert w.stream == wire * 7
    await cl.stop()


@pytest.mark.parametrize("whose", ["own", "another_client"])
async def test_armed_write_fault_takes_the_task_path(whose):
    """Any armed client.write fault keeps the pass from writing: hang
    mode needs an await. A stalled writer's backlog stays queued and
    accounted; a fault on another client only costs the wake-up."""
    broker = _broker()
    sched = broker.flush_sched
    w = RecordingWriter()
    cl = _client(broker, w, "slow")
    await _settle()
    target = "slow" if whose == "own" else "someone-else"
    faults.arm(f"{faults.CLIENT_WRITE}#{target}", "hang", count=-1,
               delay_s=30.0)
    items = [_qos0_wire(n) for n in range(6)]
    for it in items:
        _send(cl, it)
    await _settle()
    assert sched.direct == 0 and sched.woken["fault"] == 1
    if whose == "own":
        assert w.calls == []
        assert cl.outbound.qsize() == 6
        assert cl.outbound.bytes == sum(len(i) for i in items)
        faults.clear()
        cl._writer_task.cancel()
    else:
        assert w.stream == b"".join(items)
        await cl.stop()


@pytest.mark.parametrize("exc", [
    ConnectionResetError("peer gone"), OSError("no route"),
    faults.InjectedFault("injected"), RuntimeError("after write_eof")],
    ids=["connection", "oserror", "injected", "runtime"])
async def test_direct_write_that_raises_ends_that_writer_only(exc):
    """The error is recorded where the stall detector reads it, that
    client's writer ends, and nothing reaches the pass's caller: the
    next queue of the same pass is still served."""
    broker = _broker()
    sched = broker.flush_sched
    bad, good = RecordingWriter(), RecordingWriter()
    cl_bad = _client(broker, bad, "bad")
    cl_good = _client(broker, good, "good")
    await _settle()
    bad.raises = exc
    _send(cl_bad, _qos0_wire(1))
    _send(cl_good, _qos0_wire(2))
    sched.flush_now()                   # must not raise
    assert cl_bad.write_error and type(exc).__name__ in cl_bad.write_error
    assert cl_good.write_error is None
    assert good.stream == _qos0_wire(2)
    assert sched.direct == 1 and sched.woken["error"] == 1
    await _settle()
    assert cl_bad._writer_task.done()
    bad.raises = None
    await cl_bad.stop()
    await cl_good.stop()


@pytest.mark.parametrize("fault", ["hook_raises", "buffer_size_raises",
                                   "unknown_reason"])
async def test_a_faulty_owner_never_stops_the_pass(fault):
    """Whatever one queue's owner does (its hook raises, its transport
    raises where the pass asks it, it names a reason the pass does not
    know), the pass serves the queues after it, leaves none marked
    parked, and that owner's task is woken or ended: never asleep on a
    backlog."""
    broker = _broker()
    sched = broker.flush_sched
    bad, good = RecordingWriter(), RecordingWriter()
    cl_bad = _client(broker, bad, "bad")
    cl_good = _client(broker, good, "good")
    await _settle()

    def boom():
        raise RuntimeError("owner's fault")

    if fault == "hook_raises":
        cl_bad.outbound._direct = boom
    elif fault == "buffer_size_raises":
        bad.transport.get_write_buffer_size = boom
    else:
        cl_bad.outbound._direct = lambda: "because"
    _send(cl_bad, _qos0_wire(1))
    _send(cl_good, _qos0_wire(2))
    sched.flush_now()                   # must not raise
    assert good.stream == _qos0_wire(2) and sched.direct == 1
    assert not cl_bad.outbound._wake_deferred
    assert not cl_good.outbound._wake_deferred
    await _settle()
    if fault == "buffer_size_raises":   # as a write that raises
        assert "owner's fault" in cl_bad.write_error
        assert cl_bad._writer_task.done() and sched.woken["error"] == 1
    else:                               # the task took the backlog over
        assert bad.stream == _qos0_wire(1)
        want = "error" if fault == "hook_raises" else "because"
        assert sched.woken[want] == 1
    _send(cl_good, _qos0_wire(3))       # and the next pass is a pass
    await _settle()
    assert good.stream == _qos0_wire(2) + _qos0_wire(3)
    await cl_bad.stop()
    await cl_good.stop()


async def test_stop_sentinel_is_the_tasks():
    """What is queued before stop() is written in order, by the task
    that then ends; the pass writes nothing for a closed client."""
    broker = _broker()
    sched = broker.flush_sched
    w = RecordingWriter()
    cl = _client(broker, w, "bye")
    await _settle()
    _send(cl, _qos0_wire(1))
    _send(cl, _ack(7))
    await cl.stop()
    assert w.stream == _qos0_wire(1) + _ack(7)
    assert sched.direct == 0 and sched.woken["stop"] == 1
    assert cl._writer_task.done()


async def test_drain_watchers_settled_by_seq_and_progress_stamped():
    """A direct write settles the ADR-015 watchers of the deliveries it
    carried (enqueue seq <= removed) and no later one, and stamps the
    stall detector's write_progress."""
    broker = _broker()
    tracer = broker.tracer
    tracer.sample_n = 1
    sched = broker.flush_sched
    w = RecordingWriter()
    cl = _client(broker, w, "traced")
    await _settle()
    wire = bytes([0x30, 0x7F]) + b"x" * 30000
    traces = []
    for _ in range(4):                  # the cap carries three of four
        assert cl.send_wire(wire)
        tr = tracer.sample("a/b", 0, "p")
        traces.append(tr)
        cl._drain_traces.append((tr, tracer.clock(), cl.outbound.enqueued))
    cl.write_progress = before = time.monotonic() - 10.0
    w.gate = asyncio.Event()            # hold the task after its burst
    sched.flush_now()
    assert cl.outbound.removed == 3
    assert [len(tr.drains) for tr in traces] == [1, 1, 1, 0]
    assert [seq for _t, _n, seq in cl._drain_traces] == [4]
    assert cl.write_progress > before + 9.0
    assert all(c == "traced" for tr in traces for c, _s, _d in tr.drains)
    await _settle()                     # the task carries the fourth,
    assert len(w.stream) == 4 * len(wire)   # and waits in drain():
    assert [len(tr.drains) for tr in traces] == [1, 1, 1, 0]
    w.gate.set()                        # its span holds that wait
    await _settle()
    assert [len(tr.drains) for tr in traces] == [1, 1, 1, 1]
    await cl.stop()


# -- end to end: the pipeline's consumer runs the pass ------------------


async def test_pipeline_deliveries_go_direct_and_in_order():
    """QoS 0 templates, QoS 1 packets and the publisher's PUBACKs over
    real sockets with the pipeline on: every burst direct, each
    subscriber's stream in publish order."""
    async with running_broker() as broker:
        broker.attach_matcher(_TrieMatcher(broker.topics))
        s0 = await connect(broker, "s0")
        await s0.subscribe(("d/#", 0))
        s1 = await connect(broker, "s1")
        await s1.subscribe(("d/#", 1))
        pub = await connect(broker, "pub")
        await asyncio.sleep(0.05)
        sched = broker.flush_sched
        d0, woken0 = sched.direct, dict(sched.woken)
        for n in range(20):
            await pub.publish(f"d/{n}", b"%03d" % n, qos=n % 2)
        for sub in (s0, s1):
            got = [await sub.next_message(timeout=5) for _ in range(20)]
            assert [m.payload for m in got] == [b"%03d" % n
                                                for n in range(20)]
        assert sched.direct - d0 >= 20
        # no burst needed the task, but where the sender thread was
        # still writing the socket's previous one (ADR 019: it holds
        # one burst a socket at most, the task writes the next)
        woken = {r: n - woken0[r] for r, n in sched.woken.items()}
        assert woken["backpressure"] == 0 or broker.sender is not None
        assert all(n == 0 for r, n in woken.items() if r != "backpressure")
        assert not broker._pub_consumer.done()
        for c in (s0, s1, pub):
            await c.disconnect()


async def test_failed_direct_write_leaves_the_consumer_alive(monkeypatch):
    """A subscriber whose socket write raises inside the consumer's
    pass costs that subscriber alone: the next publish reaches the
    other one and the pipeline goes on. The transport's path (TLS,
    facades, no native library): the sender's own failure is
    tests/test_native_sender.py's."""
    monkeypatch.setattr(SocketSender, "start",
                        classmethod(lambda cls, loop: None))
    async with running_broker(stall_deadline_ms=0) as broker:
        broker.attach_matcher(_TrieMatcher(broker.topics))
        bad = await connect(broker, "bad")
        await bad.subscribe(("e/#", 0))
        good = await connect(broker, "good")
        await good.subscribe(("e/#", 0))
        pub = await connect(broker, "pub")
        await asyncio.sleep(0.05)
        cl_bad = broker.clients.get("bad")
        real = cl_bad.writer

        class Broken:
            transport = real.transport
            def __getattr__(self, name): return getattr(real, name)
            def writelines(self, bufs): raise BrokenPipeError("gone")
            write = writelines
        cl_bad.writer = Broken()
        await pub.publish("e/1", b"one")
        assert (await good.next_message(timeout=5)).payload == b"one"
        await poll(lambda: cl_bad.write_error is not None,
                   what="write_error recorded")
        assert "BrokenPipeError" in cl_bad.write_error
        await pub.publish("e/2", b"two")
        assert (await good.next_message(timeout=5)).payload == b"two"
        assert not broker._pub_consumer.done()
        cl_bad.writer = real
        for c in (good, pub):
            await c.disconnect()
        await bad.close()


@pytest.mark.parametrize("who", ["consumer", "call_soon"])
async def test_the_pass_is_a_sampled_publishs_flush_stage(who):
    """ADR 015 ``flush``: the pass that writes a sampled publish's parked
    deliveries is timed once, in ``flush_now``'s one body, whoever runs
    it (with a matcher the pipeline's consumer as it runs dry, else the
    ``call_soon`` pass), and lands in the entry's spans; a publish that
    reaches nobody has none; its subscribers' PUBACKs are counted."""
    async with running_broker(trace_sample_n=1, trace_ring=64) as broker:
        if who == "consumer":
            broker.attach_matcher(_TrieMatcher(broker.topics))
        subs = [await connect(broker, f"w{i}") for i in range(6)]
        for c in subs:
            await c.subscribe(("wide/#", 1))
        pub = await connect(broker, "pub")
        await asyncio.sleep(0.05)
        over, sched = broker.overload, broker.flush_sched
        acks0 = over.fanout_acks
        await pub.publish("wide/a", b"x", qos=1)
        await pub.publish("nobody/a", b"y", qos=1)
        for c in subs:
            assert (await c.next_message(timeout=5)).payload == b"x"
        await poll(lambda: over.fanout_acks - acks0 == 6, what="PUBACKs")
        assert over.fanout_widest == 6 and not sched._traced
        entries = {e["topic"]: e for e in broker.tracer.report()["entries"]}
        wide = {sp["stage"]: sp for sp in entries["wide/a"]["spans"]}
        assert "flush" in wide and wide["flush"]["parent"] == ""
        # the pass runs after the fan-out that parked the deliveries
        assert wide["flush"]["off_us"] >= wide["fanout"]["off_us"]
        assert len(entries["wide/a"]["drains"]) == 6
        assert "flush" not in {sp["stage"]
                               for sp in entries["nobody/a"]["spans"]}
        assert broker.tracer.stage_hist["flush"].count == 1
        for c in subs + [pub]:
            await c.disconnect()


async def test_flush_counters_exported():
    from maxmq_tpu.metrics import Registry, register_broker_metrics
    broker = _broker()
    sched = broker.flush_sched
    sched.direct, sched.woken["backpressure"] = 7, 2
    broker.overload.fanout_widest, broker.overload.fanout_acks = 1000, 3000
    reg = Registry()
    register_broker_metrics(reg, broker)
    text = reg.expose()
    assert "maxmq_broker_fanout_flush_direct_total 7" in text
    assert ('maxmq_broker_fanout_flush_woken_total{reason="backpressure"} 2'
            in text)
    for reason in ("fault", "facade", "stop", "error"):
        assert (f'maxmq_broker_fanout_flush_woken_total{{reason="{reason}"}}'
                ' 0') in text
    assert "maxmq_broker_fanout_widest 1000" in text
    assert "maxmq_broker_fanout_acks_total 3000" in text
    broker.hooks.stop_all()
