"""End-to-end publish-path tracing (ADR 015).

The broker's counters say *how much* work each subsystem did; nothing
before this module said *where a publish's time went*. The
:class:`PipelineTracer` stamps every Nth publish with a correlation id
and records monotonic per-stage spans across every boundary the
pipeline crosses — the asyncio loop, the matcher worker thread, the
storage writer thread, the per-client writer tasks, the cluster bridge
— then aggregates them into fixed-bucket :class:`~.metrics.Histogram`
families and keeps a bounded **flight recorder** of the slowest /
threshold-exceeding publishes with their full span breakdown.

Stage model (see docs/adr/015-publish-tracing.md for the contract):

``decode``         wire bytes -> Packet (timed in the client read loop)
``admission``      validate/ACL/overload/QoS checks in process_publish
``match_queue``    batcher coalescing wait (enqueue -> device dispatch)
``match_device``   device/trie match time (dispatch -> result ready)
``pipeline_wait``  in-order fan-out queueing behind earlier publishes
``fanout``         local subscriber selection + outbound enqueue/encode
``share_pick``     the $share picks of a fan-out alone: from its first
                   (group, filter) key to its last pick, the deliveries
                   not included (a child of ``fanout``, not critical;
                   only a publish that had a $share key has one)
``resolve``        the match result's ``resolve`` against the client
                   registry alone (ADR 007): every entry the result holds
                   probed for a session, the $share keys cut to those
                   with one; what is left of ``fanout`` without it is
                   the picks and the deliveries (a child of ``fanout``,
                   not critical)
``bridge``         cluster route consult + forward enqueue (ADR 013)
``journal_commit`` storage group-commit duration (writer thread,
                   histogram-only: not tied to one publish)
``barrier``        ack parked on the ADR-014 durability barrier
``ack``            PUBACK/PUBREC build + enqueue
``drain``          per-subscriber outbound enqueue -> writer flush
                   (completes after the publisher's e2e; capped at
                   MAX_DRAIN_SPANS subscribers per trace)
``flush``          the flush pass that wrote this publish's parked
                   deliveries (``FlushScheduler.flush_now``, from the
                   pipeline's consumer or from ``call_soon``): the whole
                   pass, every socket it wrote, since a wide fan-out's
                   cost moves there (runs after ``fanout`` closes, often
                   after the publisher's terminal stage: lands like a
                   drain then; not critical)
``takeover``       cross-node session takeover leg at CONNECT (ADR
                   016; histogram-only like journal_commit — it is a
                   connection-path span, not a publish-path one)
``bridge_in``      receiving-node inbound leg of a forwarded publish
                   (ADR 017: envelope parse + retain + fan-out handoff
                   on an ADOPTED trace — never stamped locally)
``release``        QoS2 release leg, PUBREC sent -> PUBREL received
                   (ADR 017; histogram-only like takeover — it waits
                   on the publisher's network round trip)
``filter``         content-plane batch evaluation: payload decode +
                   columnar predicate matrix + mask stamping (ADR
                   023; histogram-only, fed per pipeline flush — one
                   observation covers every publish in the batch)
``aggregate``      windowed-aggregate close + synthesized emission
                   (ADR 023; histogram-only like journal_commit — a
                   housekeeping-tick span, not a publish-path one)
``loop_lag``       a stamp scheduled with ``call_soon`` when the publish
                   was sampled -> the stamp running: how long a ready
                   callback waits for the loop at that moment (top
                   level, not critical; lands like a drain when the
                   publish finished first)
``loop_*``         the loop thread's own books (:class:`LoopLedger`): what
                   the loop spent in each state over the trailing five seconds,
                   as microseconds a publish admitted (``loop_busy``,
                   ``loop_idle``, ``loop_offcpu``, ``loop_poll``,
                   ``loop_other`` and one ``loop_<section>`` a ``maxmq.*``
                   section, self time). Not an interval of this publish:
                   a probe beside the path like ``loop_lag``; not critical,
                   no histogram, not in the Chrome export

``match_device`` ends where the answer was given and says by whom
(``via``: cache | host | trie | device | fallback); what the in-order
consumer waited past the answer is ``pipeline_wait``. While sampling is
on the batcher also keeps one :class:`BatchRecord` per micro-batch: its
phases are copied onto the batch's sampled publishes as CHILD spans of
``match_device`` (``parent``, ``batch``), outside ``CRITICAL_STAGES``:

``match_host``     the whole inline answer of a bypassed batch, as the
                   loop thread lived it (how long it blocked the loop)
``match_prep``     tokenize + exact/'+' probes + padding to the bucket
``match_probe``    the '#'-group host probe (bypass path)
``match_dispatch`` the jitted call + starting the async copies
``match_fetch``    until the result is on the host (the path's
                   ``block_until_ready``)
``match_decode``   pair assembly + batch verify + entry union
``device_rtt``     just before the jitted call -> fetched arrays on the
                   host, read on ONE executor thread (whole-batch calls
                   and shadow probes only: the pipelined path has a loop
                   hop between dispatch and fetch and records no sum);
                   contains that thread's waits for the interpreter lock
``match_hop``      result ready on the executor thread -> the loop
                   running its continuation

On the bypass path the engine answers in one native call
(``SigEngine.subscribers_host_batch``), which hands the record each part's
start (``match_prep``, ``match_probe``, ``match_decode``) only while a
record is active.

Host work is also wrapped in ``jax.profiler.TraceAnnotation``
(``maxmq.*``, :func:`host_span`), so a profiler capture holds it on the
device trace's clock; ``tools/trace_gaps.py`` reads such a capture. The
loop thread's sections open through :meth:`PipelineTracer.section`,
which feeds the annotation and the :class:`LoopLedger` from one site,
so a capture and the ledger must agree.

Cross-node model (ADR 017): a node receiving a forwarded publish whose
envelope carries trace context **adopts** the origin's trace — same
correlation id, child span chain rooted at ``bridge_in``, start
backdated to the origin's t0 translated through the per-peer clock-skew
estimate — and, on finish, fire-and-forgets its span breakdown back to
the origin over ``$cluster/trace/<origin>`` (cluster/telemetry.py),
where it lands in the origin entry's ``remote`` list and the
per-hop-count ``cross_hist`` e2e histograms.

Cost contract: with ``sample_n == 0`` every instrumented site reduces
to one attribute check/branch and **zero allocations** (asserted by
``tests/test_trace.py`` via the ``allocations`` counter, which counts
batch records too; no ``TraceAnnotation`` is built, no ``call_soon``
scheduled, the ledger's state never moves and the loop's selector is
not wrapped) — and with sampling off at the origin no trace context
crosses the wire, so the propagation path adds zero allocations
cluster-wide (asserted by ``tests/test_cluster_trace.py``). Sampling is deterministic — a stride
counter, not a PRNG — and every timestamp is read through the fault
registry's swappable ``clock_ns`` (faults.py), so tests drive spans
with a scripted clock.
"""

from __future__ import annotations

import sys
import threading
import time
import types
from collections import deque

from . import faults
from .metrics import Histogram

# a micro-batch's phases (BatchRecord): children of match_device on a
# sampled publish, never critical (a child must not be summed twice)
BATCH_PHASES = ("match_host", "match_prep", "match_probe",
                "match_dispatch", "match_fetch", "match_decode",
                "device_rtt", "match_hop")
# the maxmq.* sections the loop thread enters (PipelineTracer.section);
# the LoopLedger keeps one state each, beside idle, poll and other
LOOP_SECTIONS = ("read", "deliver", "share", "pass", "flush", "ack",
                 "batch", "settle")
LOOP_STATES = ("idle", "poll", "other") + LOOP_SECTIONS
# what a sampled publish carries of the ledger: microseconds of loop a
# publish over the trailing LEDGER_SPAN_NS. busy = poll + other + every
# section; offcpu = busy wall time the loop thread's CPU clock did not
# see. No histogram, not in the Chrome export.
LOOP_STAGES = ("loop_busy", "loop_idle", "loop_offcpu") + tuple(
    "loop_" + s for s in LOOP_STATES[1:])
# the span: long against a closed loop's generation (its messages in
# flight over its rate: 0.4 s in the fan-in and fan-out cells), since a
# sampled publish sits at the head of a burst and a span that ends there
# holds a whole number of bursts, rounded down
LEDGER_SPAN_NS = 5_000_000_000
LEDGER_SNAP_NS = 50_000_000     # least time between two of its snapshots
# canonical pipeline stages; CRITICAL_STAGES are the contiguous
# publisher-path segments whose durations sum to ~e2e (drain happens
# after the publisher's terminal stage, and so may the flush pass that
# writes it; resolve and share_pick are parts of fanout, which is
# counted whole; journal_commit/takeover/release are not tied to one
# publish's critical path; bridge_in is critical
# only on ADOPTED traces, where it IS the path's first local segment;
# loop_lag is a probe of the loop beside the path, and so are the
# ledger's loop_* spans, which are no interval of the publish at all)
FANOUT_PARTS = ("resolve", "share_pick")    # children of fanout
STAGES = ("decode", "admission", "match_queue", "match_device",
          "pipeline_wait", "filter", "fanout") + FANOUT_PARTS + (
    "bridge", "bridge_in", "journal_commit", "barrier", "ack", "drain",
    "flush", "takeover", "release", "aggregate", "loop_lag") \
    + BATCH_PHASES + LOOP_STAGES
CRITICAL_STAGES = frozenset(
    s for s in STAGES
    if s not in ("drain", "flush", "journal_commit", "takeover", "release",
                 "aggregate", "loop_lag")
    + FANOUT_PARTS + BATCH_PHASES + LOOP_STAGES)
# 10us .. 1s: a phase of one micro-batch is tens of microseconds to a
# few milliseconds, under the default ladder's first bound
BATCH_PHASE_BUCKETS = (
    0.00001, 0.000025, 0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025,
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0)

MAX_DRAIN_SPANS = 8     # per-trace cap on recorded subscriber drains
SLOWEST_KEEP = 8        # slowest-ever publishes kept beside the ring
MAX_REMOTE_REPORTS = 8  # per-entry cap on attached remote span reports
MAX_JOURNAL_BUCKETS = 16  # journal-attribution histogram families kept


# -- host spans in the profiler's own trace ------------------------------


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


NO_SPAN = _NoSpan()
_annotation = None      # jax.profiler.TraceAnnotation, on first use


def host_span(name: str, **stats):
    """A ``jax.profiler.TraceAnnotation`` around synchronous work on the
    calling thread: a profiler capture then holds it on plane
    ``/host:CPU``, on the clock of the device's events, with ``stats``
    beside it. Callers gate on ``tracer.sample_n`` and never hold one
    across an ``await`` (:func:`annotated` is for coroutines). A process
    that has not imported JAX can have no capture open, and stays off
    JAX."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return NO_SPAN
        from jax.profiler import TraceAnnotation as _annotation
    return _annotation(name, **stats)


@types.coroutine
def annotated(tracer: "PipelineTracer", name: str, coro):
    """Await ``coro`` under ``tracer.section(name)``, open only while
    the coroutine runs: closed at every real suspension and opened again
    on resume, so the section never covers another callback's work."""
    value, exc = None, None
    while True:
        with tracer.section(name):
            try:
                waited = (coro.send(value) if exc is None
                          else coro.throw(exc))
            except StopIteration as stop:
                return stop.value
        try:
            value, exc = (yield waited), None
        except BaseException as thrown:     # cancellation: the coroutine's
            value, exc = None, thrown


# -- the loop thread's own books -----------------------------------------

_IDLE, _POLL, _OTHER = (LOOP_STATES.index(s) for s in
                        ("idle", "poll", "other"))
_SECTIONS = {name: (LOOP_STATES.index(name), "maxmq." + name)
             for name in LOOP_SECTIONS}


class _Section:
    """One ``maxmq.*`` section on the loop thread: the ledger's state
    and the profiler's annotation, moved in the same order at both ends
    (the ledger's clock read, then the annotation's: a TraceAnnotation
    starts where it is built) so that the two time the same length."""

    __slots__ = ("ledger", "state", "name", "stats", "span")

    def __init__(self, ledger: "LoopLedger", state: int, name: str,
                 stats: dict) -> None:
        self.ledger = ledger
        self.state = state
        self.name = name
        self.stats = stats

    def __enter__(self):
        self.ledger.enter(self.state)
        self.span = host_span(self.name, **self.stats)
        self.span.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self.ledger.leave()
        self.span.__exit__(None, None, None)
        return False


class LoopLedger:
    """A state timer on the event loop's thread (``tracer.loop``).

    It holds what the thread is doing now (``idle`` in ``select`` with
    nothing ready, ``poll`` in a ``select`` that had ready handles
    (booked as busy), ``other``, or the ``maxmq.*`` section on top of a
    small stack), the stamp of the last change, and one cumulative
    nanosecond total and one entry count a state. A transition is one
    read of the tracer's clock: the elapsed time goes to the state that was
    current, which gives **self time** by construction (``ack`` inside
    ``read``, ``share`` inside ``deliver``, ``flush`` inside ``pass``),
    ``sum(sections) + other + poll = busy`` exactly and ``busy + idle =
    wall``. Sections are fed by :meth:`PipelineTracer.section` on the
    loop's thread alone; ``idle`` and ``poll`` by the wrapper
    :meth:`attach` puts around a stock selector loop's ``select``.
    Without it (uvloop; tracing switched on after ``serve``) ``other``
    would hold the idle time too, so ``busy``, ``idle``, ``poll``,
    ``other`` and ``offcpu`` are then absent everywhere, never zero; the
    sections are kept either way.

    Snapshots of the totals, the thread's CPU clock and the tracer's
    count of publishes are taken where a turn of the loop ends (in the
    wrapper, at most one every ``LEDGER_SNAP_NS``), and :meth:`mark`
    gives every sampled publish one span a state: the newest snapshot
    against the newest one at least ``LEDGER_SPAN_NS`` older, divided by
    the publishes admitted in between (:data:`LOOP_STAGES`). Both ends
    lie between turns, so the span holds whole turns (a turn's reads
    are admitted together), and it is long against a closed loop's
    generation (``LEDGER_SPAN_NS`` says why). Without the wrapper there
    are no turns to cut at, and the snapshot is taken at the sampled
    publish itself.
    """

    __slots__ = ("tracer", "ns", "entries", "cpu_ns", "idle_cpu_ns",
                 "cpu_clock", "tid", "_cur", "_stack", "_last", "_marks",
                 "_snapped", "_selector", "_select", "_inner")

    def __init__(self, tracer: "PipelineTracer") -> None:
        self.tracer = tracer
        self.ns = [0] * len(LOOP_STATES)        # cumulative, a state
        self.entries = [0] * len(LOOP_STATES)
        # the loop thread's CPU clock (a test scripts it): what it read
        # at the newest mark less what it counted inside idle selects,
        # i.e. the CPU time of the busy states
        self.cpu_clock = time.thread_time_ns
        self.cpu_ns = 0
        self.idle_cpu_ns = 0
        self.tid = None         # the loop's thread: serve(), else 1st mark
        self._cur = _OTHER
        self._stack: list[int] = []
        self._last = 0          # 0: paused, the next change books nothing
        self._marks: deque = deque()    # snapshots, the oldest first
        self._snapped = 0               # the stamp of the newest
        # the selector whose select is wrapped, the wrapper, what it wraps
        self._selector = self._select = self._inner = None

    @property
    def wrapped(self) -> bool:
        """``select`` is timed: idle and poll are fed, other is busy."""
        return self._selector is not None

    # -- transitions (the loop's thread) --------------------------------

    def enter(self, state: int) -> None:
        was = self._cur
        self._stack.append(was)
        self._cur = state
        self.entries[state] += 1
        # last: the annotation of the same section starts right after
        # (tracer.clock() inlined: two of these a section)
        now = (self.tracer._clock or faults.REGISTRY.clock_ns)()
        if self._last:
            self.ns[was] += now - self._last
        self._last = now

    def leave(self) -> None:
        now = (self.tracer._clock or faults.REGISTRY.clock_ns)()
        if self._last:
            self.ns[self._cur] += now - self._last
        self._last = now
        self._cur = self._stack.pop() if self._stack else _OTHER

    # -- idle and poll: the selector ------------------------------------

    def attach(self, loop) -> None:
        """``Broker.serve``, on the loop's thread: remember the thread
        and, while the tracer samples and ``loop`` is a stock selector
        loop, time its ``select``: a call with ``timeout == 0`` (ready
        handles exist: the system call, and the wait for the interpreter
        on the way back) is ``poll``, any other is ``idle``; each is
        also a ``maxmq.poll`` / ``maxmq.idle`` annotation."""
        self.tid = threading.get_ident()
        selector = getattr(loop, "_selector", None)
        if (not self.tracer.sample_n or self._selector is not None
                or not callable(getattr(selector, "select", None))):
            return
        inner = selector.select
        tracer = self.tracer

        def select(timeout=None):
            if self._selector is None or not tracer.sample_n:
                self._last = 0      # detached, or sampling switched off
                return inner(timeout)
            if timeout == 0:
                state, name, cpu = _POLL, "maxmq.poll", 0
            else:
                # the kernel's own work inside a select that sleeps is
                # on the thread's CPU clock and in no busy state
                state, name, cpu = _IDLE, "maxmq.idle", self.cpu_clock()
            self.enter(state)
            if self._last - self._snapped >= LEDGER_SNAP_NS:
                self._snapshot(self._last)      # a turn has just ended
            span = host_span(name)
            span.__enter__()
            try:
                return inner(timeout)
            finally:
                self.leave()
                span.__exit__(None, None, None)
                if state == _IDLE:
                    self.idle_cpu_ns += self.cpu_clock() - cpu

        selector.select = select
        self._selector, self._select, self._inner = selector, select, inner
        self._last = 0

    def detach(self) -> None:
        """``Broker.close``: ``select`` as it was. Another tracer's
        wrapper put over this one since stays; this one then only passes
        through."""
        selector, self._selector = self._selector, None
        if selector is None or \
                selector.__dict__.get("select") is not self._select:
            return
        if getattr(self._inner, "__self__", None) is selector:
            del selector.select         # the class's own method again
        else:
            selector.select = self._inner

    # -- the sampled publish ---------------------------------------------

    def _snapshot(self, now: int) -> None:
        """The books as of the last transition, under the stamp ``now``;
        of the older ones, the newest that is a span old is kept."""
        self._snapped = now
        self.cpu_ns = self.cpu_clock() - self.idle_cpu_ns
        marks = self._marks
        marks.append((now, self.tracer._count, self.cpu_ns,
                      tuple(self.ns), tuple(self.entries)))
        while len(marks) > 1 and now - marks[1][0] >= LEDGER_SPAN_NS:
            marks.popleft()

    def mark(self, trace: "PublishTrace") -> None:
        """``tracer.sample`` returned ``trace``: give it the trailing
        span of every state seen so far. A state that did nothing in the
        span reads a true 0; before a snapshot is ``LEDGER_SPAN_NS`` old
        nothing is attached."""
        if self.tid is None:
            self.tid = threading.get_ident()
        if not self.wrapped:
            # no turns to cut at, and no clock read of its own: the
            # totals as of the last transition (microseconds ago) under
            # the publish's own start stamp
            self._snapshot(trace.start_ns)
        marks = self._marks
        if len(marks) < 2:
            return
        then, count0, cpu0, ns0, entries0 = marks[0]
        now, count, cpu, ns, entries = marks[-1]
        if now - then < LEDGER_SPAN_NS:
            return
        n = max(count - count0, 1)
        spans = trace.loop = [
            ("loop_" + name, (ns[state] - ns0[state]) / n,
             (entries[state] - entries0[state]) / n)
            for name, (state, _span) in _SECTIONS.items()
            if entries[state]]      # one never entered reports nothing
        if not self.wrapped:
            return
        idle = ns[_IDLE] - ns0[_IDLE]
        busy = sum(ns) - sum(ns0) - idle
        turns = (entries[_IDLE] + entries[_POLL]
                 - entries0[_IDLE] - entries0[_POLL]) / n
        spans += [
            ("loop_busy", busy / n, turns),
            ("loop_idle", idle / n, (entries[_IDLE] - entries0[_IDLE]) / n),
            ("loop_offcpu", max(busy - (cpu - cpu0), 0) / n, None),
            ("loop_poll", (ns[_POLL] - ns0[_POLL]) / n,
             (entries[_POLL] - entries0[_POLL]) / n),
            ("loop_other", (ns[_OTHER] - ns0[_OTHER]) / n, None)]

    # -- for an operator ---------------------------------------------------

    def report(self) -> dict:
        """``report()["loop"]``: cumulative seconds and entries a state
        (``idle``, ``poll`` and ``other`` only while ``select`` is
        wrapped), the thread's CPU seconds as of the newest sampled
        publish, and the loop's turns."""
        states = [s for s in range(len(LOOP_STATES))
                  if self.wrapped or s > _OTHER]
        return {"wrapped": self.wrapped,
                "seconds": {LOOP_STATES[s]: self.ns[s] / 1e9
                            for s in states},
                "entries": {LOOP_STATES[s]: self.entries[s]
                            for s in states if s != _OTHER},
                "cpu_seconds": self.cpu_ns / 1e9,
                "turns": self.entries[_IDLE] + self.entries[_POLL]}


# -- micro-batch records -------------------------------------------------

_active = threading.local()


def active_batch():
    """The record of the micro-batch the calling thread is answering
    (``BatchRecord.run`` put it there), else None: how the engine's
    host half learns where to write its phases."""
    return getattr(_active, "rec", None)


class BatchRecord:
    """One micro-batch while sampling is on: id, topics, who answered
    (``via``: host | trie | device | whole), and its phases ``(name,
    t0_ns, t1_ns, on_loop)`` on the tracer's clock. ``on_loop`` says the
    loop thread recorded the phase (for the engine's phases: ran it).
    Every future of the batch points at the one record; a shadow probe
    is a record of its own (``of`` = the batch it duplicates)."""

    __slots__ = ("id", "n", "via", "of", "t0_ns", "phases", "traces",
                 "probe", "ready_ns", "closed", "tracer", "_loop_tid",
                 "_open")

    def __init__(self, tracer: "PipelineTracer", batch_id: int, n: int,
                 of: "BatchRecord | None" = None) -> None:
        self.id = batch_id
        self.n = n
        self.via = ""
        self.of = of
        self.t0_ns = tracer.clock()
        self.phases: list[tuple[str, int, int, bool]] = []
        self.traces: list[PublishTrace] = []    # its sampled publishes
        self.probe: BatchRecord | None = None   # the shadow probe of it
        self.ready_ns = 0       # result ready on the thread that ran it
        self.closed = False     # a shadow probe: every phase is in
        self.tracer = tracer
        self._loop_tid = threading.get_ident()
        self._open = None

    def begin(self, name: str) -> None:
        """Open phase ``name`` on the calling thread (one at a time)."""
        t0 = self.tracer.clock()
        span = host_span("maxmq.batch." + name.removeprefix("match_"),
                         batch=self.id, t0_ns=t0)
        span.__enter__()
        self._open = (name, t0, span)

    def end(self) -> None:
        name, t0, span = self._open
        self._open = None
        span.__exit__(None, None, None)
        self.phase(name, t0, self.tracer.clock())

    def phase(self, name: str, t0_ns: int, t1_ns: int) -> None:
        self.phases.append((name, t0_ns, t1_ns,
                            threading.get_ident() == self._loop_tid))
        self.tracer.batch_hist[name].observe(
            max(t1_ns - t0_ns, 0) / 1e9)

    def run(self, fn, *args):
        """``fn(*args)`` with this record active on the calling thread,
        then the result-ready stamp. A call that ran dispatch and fetch
        both did so back to back on this thread with no loop hop
        between: only then is ``device_rtt`` recorded."""
        first = len(self.phases)
        _active.rec = self
        try:
            return fn(*args)
        finally:
            _active.rec = None
            if self._open is not None:      # the call raised mid-phase
                self.end()
            self.ready_ns = self.tracer.clock()
            mine = {p[0]: p for p in self.phases[first:]}
            if "match_dispatch" in mine and "match_fetch" in mine:
                self.phase("device_rtt", mine["match_dispatch"][1],
                           mine["match_fetch"][2])

    def hop(self) -> None:
        """On the loop, as the continuation of an executor call runs."""
        self.phase("match_hop", self.ready_ns, self.tracer.clock())

    def last(self, name: str) -> int:
        """Nanoseconds of the newest phase ``name``, 0 where none."""
        for phase, t0, t1, _on_loop in reversed(self.phases):
            if phase == name:
                return max(t1 - t0, 0)
        return 0

    def as_dict(self) -> dict:
        out = {"id": self.id, "n": self.n, "via": self.via,
               "t0_us": self.t0_ns // 1000,
               "phases": [{"name": name,
                           "off_us": (t0 - self.t0_ns) // 1000,
                           "dur_us": max(t1 - t0, 0) // 1000,
                           "on_loop": on_loop}
                          for name, t0, t1, on_loop in self.phases]}
        if self.of is not None:
            out["shadow"] = True
            out["of"] = self.of.id
        return out


def _span_dict(start_ns: int, stage: str, t0_ns: int, dur_ns: int,
               parent: str = "", batch: int = 0, via: str = "",
               shadow: bool = False) -> dict:
    out = {"stage": stage, "off_us": (t0_ns - start_ns) // 1000,
           "dur_us": dur_ns // 1000, "parent": parent}
    if batch:
        out["batch"] = batch
    if via:
        out["via"] = via
    if shadow:
        out["shadow"] = True
    return out


class PublishTrace:
    """One sampled publish: correlation id + completed spans. Span
    endpoints are raw ``clock_ns`` stamps; nothing here allocates past
    the object itself and its lists."""

    __slots__ = ("id", "topic", "qos", "client", "start_ns", "spans",
                 "children", "via", "batch", "loop",
                 "drains", "degraded", "done", "n_drain", "entry",
                 "t_admit", "t_match", "t_barrier", "origin", "hops")

    def __init__(self, trace_id: int, topic: str, qos: int,
                 client: str, start_ns: int) -> None:
        self.id = trace_id
        self.topic = topic
        self.qos = qos
        self.client = client
        self.start_ns = start_ns
        self.spans: list[tuple[str, int, int]] = []   # (stage, t0, dur)
        # its micro-batch's phases, children of match_device:
        # (stage, t0, dur, batch id, from a shadow probe)
        self.children: list[tuple[str, int, int, int, bool]] = []
        self.via = ""           # who gave the match_device answer
        self.batch = 0          # id of the micro-batch that did
        # the LoopLedger's trailing span at this publish's sampling:
        # (loop_* stage, ns of loop a publish, entries a publish or None)
        self.loop: list[tuple[str, float, float | None]] | tuple = ()
        self.drains: list[tuple[str, int, int]] = []  # (client, t0, dur)
        self.degraded = ""      # ADR-011 rung label when not healthy
        self.done = False
        self.n_drain = 0
        self.entry = None       # live flight-recorder dict, post-finish
        # stage cursors the broker stamps between span() calls
        self.t_admit = 0
        self.t_match = 0
        self.t_barrier = 0
        # ADR 017: set only on ADOPTED traces — the node that sampled
        # the publish and how many cluster hops it took to reach here
        self.origin = ""
        self.hops = 0

    def span(self, stage: str, start_ns: int, end_ns: int) -> None:
        self.spans.append((stage, start_ns, max(end_ns - start_ns, 0)))


class PipelineTracer:
    """Per-broker publish tracer + flight recorder (ADR 015).

    ``sample_n`` is the stride (0 = off, 1 = every publish, N = every
    Nth); ``slow_ms`` > 0 restricts flight-recorder capture to
    publishes at or past that end-to-end latency (0 captures every
    sampled publish); ``ring`` bounds the recorder. Mutable at runtime
    — a harness flips ``sample_n`` between phases.

    Thread model: spans/finish run on the event loop; ``observe`` and
    ``note_error`` may fire from the storage writer thread or client
    writer tasks. Histogram/counter updates are GIL-atomic int ops;
    the ring is guarded by a lock only where the HTTP endpoints
    snapshot it.
    """

    def __init__(self, sample_n: int = 0, slow_ms: float = 0.0,
                 ring: int = 64, clock_ns=None, buckets=None) -> None:
        self.sample_n = max(int(sample_n), 0)
        self.slow_ms = float(slow_ms)
        self._clock = clock_ns          # None = fault-registry clock
        self._count = 0                 # publishes seen (stride cursor)
        self._next_id = 0
        self.sampled = 0
        self.allocations = 0            # traces allocated (the
                                        # zero-alloc-when-off witness)
        self.slow_captured = 0
        self.stage_hist: dict[str, Histogram] = {
            s: Histogram(buckets) for s in STAGES if s not in LOOP_STAGES}
        self.e2e_hist: dict[int, Histogram] = {
            q: Histogram(buckets) for q in (0, 1, 2)}
        self.stage_errors: dict[tuple[str, str], int] = {}
        self._ring: deque = deque(maxlen=max(int(ring), 1))
        self._slowest: list[dict] = []  # ascending by e2e, bounded
        # the newest micro-batch records, bounded like the ring; their
        # phases once a batch, not once a sampled publish
        self._batches: deque = deque(maxlen=max(int(ring), 1))
        self._next_batch = 0
        self.batch_hist: dict[str, Histogram] = {
            p: Histogram(BATCH_PHASE_BUCKETS) for p in BATCH_PHASES}
        self._lock = threading.Lock()
        self._buckets = buckets
        self.loop = LoopLedger(self)    # the loop thread's own books
        # -- cross-node plane (ADR 017) --------------------------------
        self.node_id = ""               # set by the cluster layer
        self.adopted = 0                # remote traces adopted here
        self.adopted_open = 0           # adopted traces not yet finished
                                        # (keeps the stamping gates open
                                        # on a node whose own sampling
                                        # is off)
        self.remote_attached = 0        # span reports attached at origin
        self.remote_orphans = 0         # reports whose trace had left
                                        # the recorder (still histogram-
                                        # fed; the ring is bounded)
        # reports that beat their trace's finish (the return leg races
        # the origin's own terminal stage): parked bounded, re-attached
        # when the trace lands in the recorder. Parking is restricted
        # to ids in _open_ids (locally sampled, not yet finished) so
        # reports for ring-evicted traces count as orphans instead of
        # rotting in (and crowding) the buffer.
        self._pending_remote: deque = deque(maxlen=64)
        self._open_ids: set[int] = set()
        # origin-measured cross-node e2e by hop count (fed by
        # attach_remote from the returned span reports)
        self.cross_hist: dict[int, Histogram] = {}
        # per-storage-bucket group-commit attribution (ADR 017 closing
        # the ADR-015 "per-op journal attribution" NOT-done item); fed
        # by the journal writer thread, bounded to MAX_JOURNAL_BUCKETS
        self.journal_hist: dict[str, Histogram] = {}
        # callback(trace, entry) fired when an ADOPTED trace finishes —
        # cluster/telemetry.py wires the span-return leg here
        self.on_adopted_finish = None

    # -- clock ----------------------------------------------------------

    def clock(self) -> int:
        """Monotonic nanoseconds via the fault registry's swappable
        clock, so a test can script every span deterministically."""
        c = self._clock
        return c() if c is not None else faults.REGISTRY.clock_ns()

    # -- hot-path entry points ------------------------------------------

    def sample(self, topic: str, qos: int, client: str,
               start_ns: int = 0) -> PublishTrace | None:
        """Admit one publish into the stride; returns a PublishTrace
        for every ``sample_n``-th call, else None. Callers gate on
        ``tracer.sample_n`` first, so an off tracer never reaches
        here."""
        n = self.sample_n
        if not n:
            return None
        self._count += 1
        if self._count % n:
            return None
        self.allocations += 1
        self.sampled += 1
        self._next_id += 1
        if len(self._open_ids) < 8192:      # rail: a site that never
            self._open_ids.add(self._next_id)   # finishes must not grow
        trace = PublishTrace(self._next_id, topic, qos, client,
                             start_ns or self.clock())
        self.loop.mark(trace)
        return trace

    def section(self, name: str, **stats):
        """A context manager around synchronous work ``maxmq.<name>``
        (one of :data:`LOOP_SECTIONS`) that never spans an ``await``
        (:func:`annotated` is for coroutines): off, ``NO_SPAN`` after the
        one attribute check; on, the :func:`host_span` of that name with
        ``stats`` and, on the loop's thread, the ledger's state."""
        if not self.sample_n:
            return NO_SPAN
        state, span_name = _SECTIONS[name]
        ledger = self.loop
        if threading.get_ident() != ledger.tid:
            # another thread's: the profiler's alone
            return host_span(span_name, **stats)
        return _Section(ledger, state, span_name, stats)

    def adopt(self, origin: str, trace_id: int, topic: str, qos: int,
              hops: int, start_ns: int) -> PublishTrace:
        """Open a child span chain for a trace SAMPLED ELSEWHERE (ADR
        017): a forwarded publish whose envelope carried trace context,
        or a pool-bus injection. Never stride-gated — the origin's
        sampling decision is authoritative cluster-wide. ``start_ns``
        is the origin's t0 translated into this node's clock frame (the
        caller applies the per-peer skew estimate), so the adopted
        trace's e2e reads as origin-publish -> local-terminal."""
        self.allocations += 1
        self.adopted += 1
        self.adopted_open += 1
        tr = PublishTrace(trace_id, topic, qos,
                          f"$cluster/{origin}", start_ns)
        tr.origin = origin
        tr.hops = hops
        return tr

    def observe(self, stage: str, seconds: float) -> None:
        """Feed one stage histogram without a per-publish trace (the
        journal's group commits)."""
        self.stage_hist[stage].observe(seconds)

    def observe_journal(self, bucket: str, seconds: float) -> None:
        """Attribute one group commit to a storage bucket it touched
        (ADR 017). Runs on the journal WRITER THREAD: dict insertion is
        GIL-atomic and the scrape path snapshots items. Bounded: past
        MAX_JOURNAL_BUCKETS distinct buckets, attribution lumps into
        ``other`` (bucket names are code-defined, so this is a rail,
        not an expected path)."""
        h = self.journal_hist.get(bucket)
        if h is None:
            if len(self.journal_hist) >= MAX_JOURNAL_BUCKETS:
                bucket = "other"
                h = self.journal_hist.get(bucket)
            if h is None:
                h = self.journal_hist.setdefault(
                    bucket, Histogram(self._buckets))
        h.observe(seconds)

    def journal_items(self) -> list:
        """Snapshot of (bucket, Histogram) for the scrape thread."""
        return sorted(self.journal_hist.items())

    def note_error(self, stage: str, reason: str = "", n: int = 1) -> None:
        """Attribute an error/drop to a pipeline stage — the counter
        behind ``maxmq_broker_stage_errors_total{stage=,reason=}``.
        Locked: callers include the storage writer thread, and a bare
        dict read-modify-write racing the scrape thread's iteration
        could lose increments or blow up the whole exposition."""
        key = (stage, reason)
        with self._lock:
            self.stage_errors[key] = self.stage_errors.get(key, 0) + n

    def stage_error_items(self) -> list:
        """Snapshot of (stage, reason) -> count for the scrape thread
        (iterating the live dict could race a first-seen insert from
        another thread)."""
        with self._lock:
            return list(self.stage_errors.items())

    def open_batch(self, n: int,
                   of: BatchRecord | None = None) -> BatchRecord:
        """A record for one micro-batch of ``n`` topics (callers gate on
        ``sample_n``); ``of`` makes it the shadow probe of that batch.
        Runs on the loop: the deque's append is all the scrape thread
        can race."""
        self.allocations += 1
        self._next_batch += 1
        rec = BatchRecord(self, self._next_batch, n, of)
        if of is not None:
            of.probe = rec
        self._batches.append(rec)
        return rec

    def batch_spans(self, trace: PublishTrace, rec: BatchRecord) -> None:
        """Copy the phases of the batch that answered ``trace`` onto it
        as child spans, with those of its shadow probe when that has
        ended; one that ends later reaches the trace through
        ``close_shadow``."""
        trace.batch = rec.id
        rec.traces.append(trace)
        for owner in (rec, rec.probe):
            if owner is None or (owner is not rec and not owner.closed):
                continue
            for name, t0, t1, _on_loop in owner.phases:
                trace.children.append((name, t0, max(t1 - t0, 0),
                                       owner.id, owner is not rec))

    def close_shadow(self, probe: BatchRecord) -> None:
        """A shadow probe's last phase is in: its phases go to the
        sampled publishes of the batch it duplicated that were traced
        already, finished or not."""
        probe.closed = True
        for trace in probe.of.traces:
            for name, t0, t1, _on_loop in probe.phases:
                self.attach(trace, name, t0, t1, probe.id, True)

    def attach(self, trace: PublishTrace, stage: str, start_ns: int,
               end_ns: int, batch: int = 0, shadow: bool = False) -> None:
        """One span for a publish that may have finished already:
        ``loop_lag`` or ``flush`` (top level) or, with ``batch``, a phase
        of that micro-batch (child of ``match_device``). After the finish
        it feeds the histogram and is appended to the live
        flight-recorder entry, the way ``drain_span`` appends drains."""
        dur = max(end_ns - start_ns, 0)
        if not trace.done:
            if batch:
                trace.children.append((stage, start_ns, dur, batch, shadow))
            else:
                trace.spans.append((stage, start_ns, dur))
            return
        self.stage_hist[stage].observe(dur / 1e9)
        entry = trace.entry
        if entry is not None:
            entry["spans"].append(_span_dict(
                trace.start_ns, stage, start_ns, dur,
                "match_device" if batch else "", batch, "", shadow))

    def drain_span(self, trace: PublishTrace, client: str,
                   start_ns: int, end_ns: int) -> None:
        """One subscriber's outbound enqueue->writer-flush span; lands
        after the publisher-path finish, so it feeds the histogram and
        is appended to the live flight-recorder entry when one holds
        this trace."""
        dur = max(end_ns - start_ns, 0)
        self.stage_hist["drain"].observe(dur / 1e9)
        trace.drains.append((client, start_ns, dur))
        entry = trace.entry
        if entry is not None:
            entry["drains"].append(
                {"client": client,
                 "off_us": (start_ns - trace.start_ns) // 1000,
                 "dur_us": dur // 1000})

    # -- completion -----------------------------------------------------

    def finish(self, trace: PublishTrace, end_ns: int = 0) -> None:
        """Terminal stage reached: feed the histograms and decide
        flight-recorder capture. Idempotent (the durable-ack and
        direct paths can both reach it on teardown races). An ADOPTED
        trace always records (the origin already paid the sampling
        decision and will correlate against it) and fires the
        span-return callback once recorded."""
        if trace.done:
            return
        trace.done = True
        adopted = bool(trace.origin)
        if adopted:
            self.adopted_open = max(self.adopted_open - 1, 0)
        end = end_ns or self.clock()
        e2e_ns = max(end - trace.start_ns, 0)
        hist = self.stage_hist
        for stage, _t0, dur in trace.spans:
            hist[stage].observe(dur / 1e9)
        for stage, _t0, dur, _batch, _shadow in trace.children:
            hist[stage].observe(dur / 1e9)
        if not adopted:
            # adopted e2e is origin-publish -> local-terminal across
            # network hops and a skew estimate: it belongs to the
            # cross-node family (fed at the origin from the returned
            # report), NOT to this node's local publisher-path e2e
            self.e2e_hist[min(trace.qos, 2)].observe(e2e_ns / 1e9)
            self._open_ids.discard(trace.id)
        slow = self.slow_ms > 0 and e2e_ns >= self.slow_ms * 1e6
        if slow:
            self.slow_captured += 1
        if not slow and self.slow_ms > 0 and not adopted:
            return                      # under threshold: not recorded
        entry = self._entry(trace, e2e_ns, slow)
        trace.entry = entry
        with self._lock:
            self._ring.append(entry)
            self._note_slowest(entry)
        self._post_record(trace, entry, adopted)

    def _post_record(self, trace: PublishTrace, entry: dict,
                     adopted: bool) -> None:
        """After an entry lands in the recorder: claim any remote span
        reports that beat the finish, and fire the ADR-017 span-return
        callback for adopted traces."""
        if not adopted and self._pending_remote:
            late = [r for r in self._pending_remote
                    if r.get("i") == trace.id]
            for r in late:
                self._pending_remote.remove(r)
                self._attach_to_entries(r)
        cb = self.on_adopted_finish
        if adopted and cb is not None:
            cb(trace, entry)

    @staticmethod
    def _entry(trace: PublishTrace, e2e_ns: int, slow: bool) -> dict:
        start = trace.start_ns
        spans = [_span_dict(start, s, t0, dur, "", trace.batch, trace.via)
                 if s == "match_device" else
                 _span_dict(start, s, t0, dur,
                            "fanout" if s in FANOUT_PARTS else "")
                 for s, t0, dur in trace.spans]
        spans += [_span_dict(start, s, t0, dur, "match_device", batch,
                             "", shadow)
                  for s, t0, dur, batch, shadow in trace.children]
        for stage, per_publish_ns, calls in trace.loop:
            span = {"stage": stage, "off_us": 0,
                    "dur_us": round(per_publish_ns / 1000, 1), "parent": ""}
            if calls is not None:
                span["calls"] = round(calls, 3)
            spans.append(span)
        critical_ns = sum(dur for s, _t0, dur in trace.spans
                          if s in CRITICAL_STAGES)
        entry = {"id": trace.id, "topic": trace.topic, "qos": trace.qos,
                 "client": trace.client, "start_us": start // 1000,
                 "e2e_ms": round(e2e_ns / 1e6, 3),
                 "critical_sum_ms": round(critical_ns / 1e6, 3),
                 "slow": slow, "degraded": trace.degraded,
                 "spans": spans,
                 "drains": [{"client": c, "off_us": (t0 - start) // 1000,
                             "dur_us": d // 1000}
                            for c, t0, d in trace.drains]}
        if trace.origin:
            entry["origin"] = trace.origin
            entry["hops"] = trace.hops
        return entry

    # -- cross-node span returns (ADR 017) -----------------------------

    def attach_remote(self, report: dict) -> bool:
        """Land one returned span report on the origin's own entry:
        ``report`` is the telemetry-decoded ``$cluster/trace`` payload
        ({i: trace id, n: reporter node, h: hops, e2e_us, spans, deg,
        k}). Feeds the per-hop cross-node e2e histogram either way; a
        report that BEAT its trace's finish is parked (bounded) and
        re-attached from finish(); one whose trace already left the
        recorder is counted and dropped."""
        hops = max(int(report.get("h", 1)), 1)
        e2e_us = max(int(report.get("e2e_us", 0)), 0)
        if report.get("k", "pub") == "pub":
            # only publish-path reports feed the per-hop e2e histogram
            # (sess_ship legs would skew the publish tail)
            h = self.cross_hist.get(hops)
            if h is None:
                h = self.cross_hist.setdefault(
                    hops, Histogram(self._buckets))
            h.observe(e2e_us / 1e6)
        if self._attach_to_entries(report):
            return True
        tid = report.get("i")
        if tid in self._open_ids:
            # a locally-sampled trace that has not finished yet: park
            # for finish() to claim; bounded, eviction = orphan
            if len(self._pending_remote) == self._pending_remote.maxlen:
                self.remote_orphans += 1
            self._pending_remote.append(report)
        else:
            self.remote_orphans += 1    # evicted/unknown trace
        return False

    def _attach_to_entries(self, report: dict) -> bool:
        tid, node = report.get("i"), str(report.get("n", ""))
        hops = max(int(report.get("h", 1)), 1)
        e2e_us = max(int(report.get("e2e_us", 0)), 0)
        with self._lock:
            entry = next(
                (e for e in list(self._ring) + self._slowest
                 if e["id"] == tid and "origin" not in e), None)
            if entry is None:
                return False
            remote = entry.setdefault("remote", [])
            if (len(remote) >= MAX_REMOTE_REPORTS
                    or any(r["node"] == node for r in remote)):
                return True     # handled: duplicate/full, not orphaned
            remote.append({
                "node": node, "hops": hops,
                "e2e_ms": round(e2e_us / 1e3, 3),
                "degraded": str(report.get("deg", "")),
                "spans": [{"stage": str(s), "off_us": int(o),
                           "dur_us": int(d)}
                          for s, o, d in report.get("spans") or []]})
            self.remote_attached += 1
        return True

    def _note_slowest(self, entry: dict) -> None:
        """Keep the SLOWEST_KEEP slowest entries ever seen, ascending,
        beside the recency ring (a burst of slow publishes must not
        evict the all-time outlier). Under self._lock."""
        sl = self._slowest
        if len(sl) >= SLOWEST_KEEP and entry["e2e_ms"] <= sl[0]["e2e_ms"]:
            return
        sl.append(entry)
        sl.sort(key=lambda e: e["e2e_ms"])
        del sl[:-SLOWEST_KEEP]

    # -- reporting ------------------------------------------------------

    @property
    def ring_depth(self) -> int:
        return len(self._ring)

    def stage_quantiles(self, qs=(0.5, 0.95, 0.99)) -> dict:
        """{stage: {count, p50_ms, ...}} over stages with data — the
        ``trace`` stanza of a day harness's SLO sheet and of the
        $SYS/HTTP trace surface."""
        out: dict = {}
        for stage, h in self.stage_hist.items():
            if not h.count:
                continue
            row = {"count": h.count}
            for q in qs:
                row[f"p{int(q * 100)}_ms"] = round(
                    h.quantile(q) * 1e3, 3)
            out[stage] = row
        return out

    def e2e_quantiles(self, qs=(0.5, 0.95, 0.99)) -> dict:
        out: dict = {}
        for qos, h in self.e2e_hist.items():
            if not h.count:
                continue
            row = {"count": h.count}
            for q in qs:
                row[f"p{int(q * 100)}_ms"] = round(
                    h.quantile(q) * 1e3, 3)
            out[f"qos{qos}"] = row
        return out

    def cross_quantiles(self, qs=(0.5, 0.95, 0.99)) -> dict:
        """Origin-measured cross-node e2e by hop count (ADR 017): the
        per-hop attribution row."""
        out: dict = {}
        for hops, h in sorted(self.cross_hist.items()):
            if not h.count:
                continue
            row = {"count": h.count}
            for q in qs:
                row[f"p{int(q * 100)}_ms"] = round(
                    h.quantile(q) * 1e3, 3)
            out[f"hops{hops}"] = row
        return out

    def report(self) -> dict:
        """The ``/traces`` endpoint body: config, aggregate quantiles,
        the recency ring (oldest first) and the slowest-ever list."""
        with self._lock:
            entries = list(self._ring)
            slowest = list(self._slowest)
        batches = [rec.as_dict() for rec in list(self._batches)]
        return {"sample_n": self.sample_n, "slow_ms": self.slow_ms,
                "node": self.node_id,
                "sampled": self.sampled,
                "slow_captured": self.slow_captured,
                "adopted": self.adopted,
                "remote_attached": self.remote_attached,
                "remote_orphans": self.remote_orphans,
                "stage_quantiles": self.stage_quantiles(),
                "e2e_quantiles": self.e2e_quantiles(),
                "cross_node": self.cross_quantiles(),
                "entries": entries, "slowest": slowest,
                "batches": batches, "loop": self.loop.report()}

    def chrome_events(self) -> dict:
        """The ``/traces/chrome`` endpoint body: flight-recorder
        entries as Chrome trace_event JSON (load in chrome://tracing
        or Perfetto). One complete ('X') event per span, one PROCESS
        ROW PER NODE (ADR 017: attached remote span reports render on
        their reporter's own named track, offsets already translated
        into the origin's timeline), one thread row per publish."""
        with self._lock:
            entries = list(self._ring)
            for e in self._slowest:
                if all(e["id"] != r["id"] for r in entries):
                    entries.append(e)
        events = []
        node_pids = {self.node_id or "local": 1}

        def pid_for(node: str) -> int:
            pid = node_pids.get(node)
            if pid is None:
                pid = node_pids[node] = len(node_pids) + 1
            return pid

        for e in entries:
            args = {"topic": e["topic"], "qos": e["qos"],
                    "client": e["client"], "e2e_ms": e["e2e_ms"],
                    "degraded": e["degraded"]}
            if "origin" in e:
                args["origin"] = e["origin"]
                args["hops"] = e["hops"]
            events.append({"name": f"publish #{e['id']}",
                           "cat": "publish", "ph": "X",
                           "ts": e["start_us"],
                           "dur": int(e["e2e_ms"] * 1000),
                           "pid": 1, "tid": e["id"], "args": args})
            for sp in e["spans"] + e["drains"]:
                if sp.get("stage") in LOOP_STAGES:
                    continue        # a rate, not an interval of this publish
                events.append({
                    "name": sp.get("stage",
                                   f"drain:{sp.get('client', '')}"),
                    "cat": "publish", "ph": "X",
                    "ts": e["start_us"] + sp["off_us"],
                    "dur": max(sp["dur_us"], 1),
                    "pid": 1, "tid": e["id"], "args": {}})
            self._remote_events(e, pid_for, events)
        meta = [{"name": "process_name", "ph": "M", "pid": pid,
                 "args": {"name": f"node {node}"}}
                for node, pid in node_pids.items()]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    @staticmethod
    def _remote_events(e: dict, pid_for, events: list) -> None:
        """Attached remote span reports as events on the reporter's
        own process track (ADR 017)."""
        for r in e.get("remote", ()):
            pid = pid_for(r["node"])
            events.append({
                "name": f"publish #{e['id']} @{r['node']}",
                "cat": "publish", "ph": "X", "ts": e["start_us"],
                "dur": max(int(r["e2e_ms"] * 1000), 1),
                "pid": pid, "tid": e["id"],
                "args": {"hops": r["hops"],
                         "degraded": r["degraded"]}})
            for sp in r["spans"]:
                events.append({
                    "name": sp["stage"], "cat": "publish", "ph": "X",
                    "ts": e["start_us"] + sp["off_us"],
                    "dur": max(sp["dur_us"], 1),
                    "pid": pid, "tid": e["id"], "args": {}})

    def sys_entries(self) -> dict:
        """The ``$SYS/broker/trace/*`` subtree (server.py publishes it
        while tracing is on)."""
        e2e = self.e2e_quantiles()
        entries = {
            "$SYS/broker/trace/sample_n": self.sample_n,
            "$SYS/broker/trace/slow_ms": self.slow_ms,
            "$SYS/broker/trace/sampled": self.sampled,
            "$SYS/broker/trace/slow": self.slow_captured,
            "$SYS/broker/trace/ring_depth": self.ring_depth,
            "$SYS/broker/trace/stage_errors":
                sum(n for _k, n in self.stage_error_items()),
            "$SYS/broker/trace/adopted": self.adopted,
            "$SYS/broker/trace/remote_attached": self.remote_attached,
        }
        for qos, row in e2e.items():
            entries[f"$SYS/broker/trace/e2e/{qos}_p99_ms"] = \
                row["p99_ms"]
        loop = self.loop.report()
        for state, seconds in loop["seconds"].items():
            entries[f"$SYS/broker/trace/loop/{state}_seconds"] = \
                round(seconds, 6)
        entries["$SYS/broker/trace/loop/cpu_seconds"] = \
            round(loop["cpu_seconds"], 6)
        entries["$SYS/broker/trace/loop/turns"] = loop["turns"]
        return entries
