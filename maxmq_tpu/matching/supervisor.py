"""Matcher degradation ladder: deadline → trie hedge → breaker → reprobe.

The device matchers (the sig engines, the MicroBatcher over them, the
ServiceMatcher socket client) degrade to the CPU trie on *row overflow*
— but a device error, a hung kernel, a failed recompile, or a dead
matcher-service socket used to surface as an exception (or a stall)
inside the publish path. The SupervisedMatcher (ADR 011) wraps any of
them so publishes always complete, with results bit-equal to the CPU
trie (the trie is the ground truth every device path already proves
itself against):

1. **Per-batch deadline** — every device/service call is raced against
   ``deadline_ms``; a call that hangs past it is abandoned and the
   batch is answered from the trie (reason="deadline").
2. **Trie hedge on error** — a call that raises is answered from the
   trie (reason="error"); the exception is recorded, never re-raised
   into the publish pipeline.
3. **Circuit breaker** — ``breaker_threshold`` failures within
   ``breaker_window_s`` trip the matcher to trie-only mode
   (reason="breaker_open"): no more device calls, no more hung threads,
   bounded tail latency while the device path is sick.
4. **Half-open reprobe** — after an exponential backoff
   (``backoff_initial_s`` doubling to ``backoff_max_s``) exactly one
   live request is routed to the device as a probe; success closes the
   breaker and restores the device path, failure re-opens it with a
   doubled backoff.

``refresh()`` is crash-safe: a failed recompile keeps serving the
last-good tables (and counts toward the breaker) instead of raising.

On the async surface (``enqueue``, the publish pipeline's) the ladder is
bookkeeping beside the inner matcher's own future, not a wrapper around
it (ADR 011, addendum): the deadlines of all topics in flight stand in
one queue served by one timer, and an inner that reports a failed batch
(``on_batch_failed``: the MicroBatcher, the ServiceMatcher) has its
topics answered from the trie on the futures the pipeline already
holds. An inner without that surface keeps a second future per topic
(``wrapped_topics``) on the same queue and the same accounting.

Observability: ``breaker_state`` (0 closed / 1 open / 2 half-open),
``fallbacks_by_reason`` (overflow / error / deadline / breaker_open),
``degraded_seconds``, ``breaker_trips``, ``refresh_failures`` — all
exported by metrics.py as the ``maxmq_matcher_breaker_*`` family and
the reason-labelled ``maxmq_matcher_fallbacks_total``.

Everything else (stats, ``engine``, ``index``, forwarding surfaces,
``close``) delegates to the wrapped matcher, so the supervisor is a
drop-in for ``broker.attach_matcher`` and the metrics bridge.
"""

from __future__ import annotations

import asyncio
import collections
import threading
import time

BREAKER_CLOSED = 0
BREAKER_OPEN = 1
BREAKER_HALF_OPEN = 2

_STATE_NAMES = {BREAKER_CLOSED: "closed", BREAKER_OPEN: "open",
                BREAKER_HALF_OPEN: "half_open"}

def fail_batch(inner, batch, exc: Exception) -> None:
    """What an inner matcher with the ``on_batch_failed`` surface does
    with ``(topic, future)`` pairs whose answer raised: tell the
    observer once (the supervisor answers them from the CPU trie), then
    fail whatever it left pending — all of them with no observer."""
    if inner.on_batch_failed is not None:
        inner.on_batch_failed(batch, exc)
    for _, fut in batch:
        if not fut.done():
            fut.set_exception(exc)


# ``_probe_fut`` while the inner's enqueue runs for the half-open probe:
# its future does not exist yet, and a failure reported now is its own
_PROBE_UNBORN = object()


class SupervisedMatcher:
    """Wrap ``inner`` (engine / MicroBatcher / ServiceMatcher) in the
    ADR-011 degradation ladder. ``index`` overrides the trie used for
    degraded answers; by default ``inner.index`` serves (exact by
    construction — every engine's ground truth)."""

    def __init__(self, inner, deadline_ms: float = 250.0,
                 breaker_threshold: int = 5,
                 breaker_window_s: float = 10.0,
                 backoff_initial_s: float = 1.0,
                 backoff_max_s: float = 30.0,
                 index=None, logger=None) -> None:
        self.inner = inner
        self.deadline_ms = float(deadline_ms)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_window_s = float(breaker_window_s)
        self.backoff_initial_s = float(backoff_initial_s)
        self.backoff_max_s = float(backoff_max_s)
        self._index = index
        self._log = logger
        self._lock = threading.Lock()
        self._failures: collections.deque[float] = collections.deque()
        self._state = BREAKER_CLOSED
        self._open_until = 0.0
        self._backoff = self.backoff_initial_s
        self._probe_inflight = False
        self._degraded_since: float | None = None
        self._degraded_total = 0.0
        # counters (scraped by the metrics bridge; see fallbacks_by_reason)
        self.deadline_fallbacks = 0
        self.error_fallbacks = 0
        self.breaker_fallbacks = 0
        self.refresh_failures = 0
        self.breaker_trips = 0
        self.breaker_recoveries = 0
        # the async surface's deadline queue: (due, future, topic, probe)
        # in enqueue order, which is due order (one constant deadline),
        # and the ONE timer armed for its head
        self._watched: collections.deque[tuple] = collections.deque()
        self._sweep_timer: asyncio.TimerHandle | None = None
        self._sweep_loop: asyncio.AbstractEventLoop | None = None
        self.deadline_timers_armed = 0    # each runs one sweep
        self.wrapped_topics = 0           # topics that took a second future
        # the future the half-open probe rides (_PROBE_UNBORN while the
        # inner's enqueue is being called for it)
        self._probe_fut = None
        # an inner that reports a failed batch needs no wrapper: its
        # futures are answered from the trie where it learns of the
        # failure, one call a batch
        self._direct = (hasattr(inner, "on_batch_failed")
                        and hasattr(inner, "enqueue"))
        if self._direct:
            inner.on_batch_failed = self._batch_failed

    # -- delegation ----------------------------------------------------

    def __getattr__(self, name):
        # only consulted for names NOT defined on this class: stats,
        # engine, forward_* surfaces, close, warm hooks, ... all pass
        # straight through to the wrapped matcher
        if name == "inner":           # unpickling / pre-__init__ access
            raise AttributeError(name)
        if name == "refresh":
            # crash-safe refresh, but ONLY when the inner matcher has
            # one: defining it unconditionally would make duck-typing
            # probes (getattr(matcher, "refresh", None) in the boot
            # compile) call into a refresh-less ServiceMatcher and
            # count a spurious breaker failure on a healthy boot
            inner_refresh = self.inner.refresh  # AttributeError if absent
            return lambda force=False: self._safe_refresh(inner_refresh,
                                                          force)
        return getattr(self.inner, name)

    @property
    def index(self):
        return self._index if self._index is not None \
            else getattr(self.inner, "index", None)

    def _inner_overflow(self) -> int:
        # ``overflow_fallbacks`` lets an inner matcher exclude fallback
        # events the SUPERVISOR already counts: a ServiceMatcher's
        # dead-transport fast-fails surface here as reason="error", so
        # counting its ``fallbacks`` under "overflow" too would both
        # double the total and invent an overflow problem mid-outage
        return int(getattr(self.inner, "overflow_fallbacks",
                           getattr(self.inner, "fallbacks", 0)))

    @property
    def fallbacks(self):
        """Total trie fallbacks, all reasons — the pre-ADR-011 counter
        (see docs/migration.md: split by reason under the hood)."""
        return (self._inner_overflow() + self.deadline_fallbacks
                + self.error_fallbacks + self.breaker_fallbacks)

    @property
    def fallbacks_by_reason(self) -> dict[str, int]:
        return {"overflow": self._inner_overflow(),
                "error": self.error_fallbacks,
                "deadline": self.deadline_fallbacks,
                "breaker_open": self.breaker_fallbacks}

    # -- breaker state machine -----------------------------------------

    @property
    def breaker_state(self) -> int:
        return self._state

    @property
    def breaker_state_name(self) -> str:
        return _STATE_NAMES[self._state]

    @property
    def degraded_seconds(self) -> float:
        """Cumulative wall time spent with the breaker not closed."""
        with self._lock:
            total = self._degraded_total
            if self._degraded_since is not None:
                total += time.monotonic() - self._degraded_since
            return total

    def _admit(self) -> str:
        """Route one call: 'device' (closed), 'probe' (the single
        half-open reprobe), or 'trie' (open / probe already in flight)."""
        if self._state == BREAKER_CLOSED:
            return "device"
        with self._lock:
            if self._state == BREAKER_CLOSED:
                return "device"
            now = time.monotonic()
            if self._state == BREAKER_OPEN and now >= self._open_until \
                    and not self._probe_inflight:
                self._state = BREAKER_HALF_OPEN
                self._probe_inflight = True
                return "probe"
            if self._state == BREAKER_HALF_OPEN \
                    and not self._probe_inflight:
                self._probe_inflight = True
                return "probe"
            return "trie"

    def _record_failure(self, probe: bool) -> None:
        with self._lock:
            now = time.monotonic()
            if probe:
                # failed reprobe: back off harder before the next one
                self._probe_inflight = False
                self._backoff = min(self._backoff * 2, self.backoff_max_s)
                self._state = BREAKER_OPEN
                self._open_until = now + self._backoff
                return
            self._failures.append(now)
            cutoff = now - self.breaker_window_s
            while self._failures and self._failures[0] < cutoff:
                self._failures.popleft()
            if self._state == BREAKER_CLOSED \
                    and len(self._failures) >= self.breaker_threshold:
                self._state = BREAKER_OPEN
                self._backoff = self.backoff_initial_s
                self._open_until = now + self._backoff
                self._degraded_since = now
                self.breaker_trips += 1
                self._warn("matcher breaker OPEN: trie-only mode",
                           failures=len(self._failures),
                           backoff_s=self._backoff)

    def _record_success(self, probe: bool) -> None:
        if not probe:
            return
        with self._lock:
            self._probe_inflight = False
            if self._state != BREAKER_CLOSED:
                self._state = BREAKER_CLOSED
                self._failures.clear()
                self._backoff = self.backoff_initial_s
                if self._degraded_since is not None:
                    self._degraded_total += (time.monotonic()
                                             - self._degraded_since)
                    self._degraded_since = None
                self.breaker_recoveries += 1
                self._warn("matcher breaker CLOSED: device path restored")

    def _probe_abort(self) -> None:
        """A probe that was cancelled (shutdown) neither succeeded nor
        failed: release the slot so the next call can reprobe."""
        with self._lock:
            self._probe_inflight = False
            if self._state == BREAKER_HALF_OPEN:
                self._state = BREAKER_OPEN

    def _warn(self, msg: str, **kw) -> None:
        if self._log is not None:
            self._log.warn(msg, **kw)

    # -- degraded answers ----------------------------------------------

    def _trie(self, topic: str):
        idx = self.index
        if idx is None:
            raise RuntimeError(
                "supervised matcher has no index for trie fallback")
        return idx.subscribers(topic)

    def _trie_batch(self, topics: list[str]) -> list:
        idx = self.index
        if idx is None:
            raise RuntimeError(
                "supervised matcher has no index for trie fallback")
        return [idx.subscribers(t) for t in topics]

    # -- crash-safe refresh --------------------------------------------

    def _safe_refresh(self, inner_refresh, force: bool = False):
        """Recompile via the inner engine (exposed as ``refresh`` when
        the inner matcher has one — see __getattr__); a failed
        recompile keeps the last-good tables serving (and counts toward
        the breaker — a device path that can't compile shouldn't keep
        being probed per publish) instead of raising into the caller."""
        try:
            return inner_refresh(force=force)
        except Exception as exc:
            self.refresh_failures += 1
            self._record_failure(probe=False)
            self._warn("matcher recompile failed; serving last-good "
                       "tables", error=repr(exc)[:200])
            return False

    # -- sync surface ---------------------------------------------------

    def subscribers(self, topic: str):
        return self.subscribers_batch([topic])[0]

    def _inner_batch(self, topics: list[str]) -> list:
        fn = getattr(self.inner, "subscribers_batch", None)
        if fn is not None:
            return fn(topics)
        return [self.inner.subscribers(t) for t in topics]

    def _race_deadline(self, topics: list[str]):
        """Run the inner batch in a DAEMON thread raced against the
        deadline: a call that never returns must not block interpreter
        exit (a pooled non-daemon worker would hang the atexit join —
        the exact wedge the deadline exists for), and each timed-out
        call counts as a failure, so the breaker stops spawning these
        long before hung threads accumulate. Returns ("ok", results) |
        ("err", exc) | ("timeout", None)."""
        box: list = []
        done = threading.Event()

        def runner() -> None:
            try:
                box.append(("ok", self._inner_batch(topics)))
            except BaseException as exc:
                box.append(("err", exc))
            finally:
                done.set()

        threading.Thread(target=runner, daemon=True,
                         name="matcher-supervisor").start()
        if not done.wait(self.deadline_ms / 1e3):
            return ("timeout", None)
        return box[0]

    def subscribers_batch(self, topics: list[str]) -> list:
        route = self._admit()
        if route == "trie":
            self.breaker_fallbacks += len(topics)
            return self._trie_batch(topics)
        probe = route == "probe"
        if self.deadline_ms <= 0:
            try:
                results = self._inner_batch(topics)
            except Exception:
                self._record_failure(probe)
                self.error_fallbacks += len(topics)
                return self._trie_batch(topics)
            self._record_success(probe)
            return results
        status, value = self._race_deadline(list(topics))
        if status == "timeout":
            self._record_failure(probe)
            self.deadline_fallbacks += len(topics)
            return self._trie_batch(topics)
        if status == "err":
            self._record_failure(probe)
            self.error_fallbacks += len(topics)
            return self._trie_batch(topics)
        self._record_success(probe)
        return value

    # -- async surface (the broker publish pipeline) --------------------

    def _inner_enqueue(self, topic: str) -> asyncio.Future:
        enq = getattr(self.inner, "enqueue", None)
        if enq is not None:
            return enq(topic)
        sub_async = getattr(self.inner, "subscribers_async", None)
        if sub_async is not None:
            return asyncio.ensure_future(sub_async(topic))
        loop = asyncio.get_running_loop()
        return loop.run_in_executor(None, self.inner.subscribers, topic)

    def enqueue(self, topic: str) -> asyncio.Future:
        """The ADR-006 pipeline surface: returns a future that ALWAYS
        resolves by the deadline — device result, or trie answer on
        error / deadline / open breaker. With an inner that reports its
        failed batches it is the inner's own future: its awaiter wakes
        in the loop iteration after the answer was given."""
        route = self._admit()
        if route == "trie":
            self.breaker_fallbacks += 1
            return self._trie_future(topic, None)
        probe = route == "probe"
        if not self._direct:
            return self._enqueue_wrapped(topic, probe)
        if probe:
            # a failure reported from inside the call is the probe's
            self._probe_fut = _PROBE_UNBORN
        try:
            fut = self.inner.enqueue(topic)
        except Exception as exc:
            self._probe_fut = None
            self._record_failure(probe)
            self.error_fallbacks += 1
            return self._trie_future(topic, exc)
        if probe and self._probe_fut is _PROBE_UNBORN:
            self._probe_fut = fut
            fut.add_done_callback(self._probe_done)
        if not fut.done():
            self._watch(fut, topic, probe)
        return fut

    def _enqueue_wrapped(self, topic: str, probe: bool) -> asyncio.Future:
        """An inner that cannot say when a batch failed (an engine in
        the executor, ``subscribers_async``, a bare ``enqueue``): a
        second future stands between its exception and the pipeline.
        The deadline queue and the accounting are the direct path's."""
        self.wrapped_topics += 1
        try:
            inner = self._inner_enqueue(topic)
        except Exception as exc:
            self._record_failure(probe)
            self.error_fallbacks += 1
            return self._trie_future(topic, exc)
        out: asyncio.Future = asyncio.get_running_loop().create_future()

        def done(f: asyncio.Future) -> None:
            if f.cancelled():
                # shutdown-path cancel, not a device failure
                if probe:
                    self._probe_abort()
                if not out.done():
                    out.cancel()
                return
            exc = f.exception()
            if out.done():
                # late completion after the deadline already answered
                # (or the caller went away): result/exception discarded,
                # failure (if any) was recorded when the deadline fired
                return
            if exc is not None:
                self._record_failure(probe)
                self.error_fallbacks += 1
                self._settle_from_trie(out, topic, exc)
            else:
                self._record_success(probe)
                # the ADR-015 marks an inner stamped on ITS future
                for attr in ("_t_dispatch", "_t_done", "_t_batch",
                             "_t_via"):
                    v = getattr(f, attr, 0)
                    if v:
                        setattr(out, attr, v)
                out.set_result(f.result())

        inner.add_done_callback(done)
        self._watch(out, topic, probe)
        return out

    def _probe_done(self, fut: asyncio.Future) -> None:
        """The one done-callback of the direct path: the half-open
        probe's future finished. Nothing to do where the deadline or the
        error hedge judged the probe first."""
        if self._probe_fut is not fut:
            return
        self._probe_fut = None
        if fut.cancelled():
            self._probe_abort()     # shutdown: neither success nor failure
        elif fut.exception() is None:
            self._record_success(True)
        else:
            self._record_failure(True)

    def _batch_failed(self, batch, exc: Exception) -> None:
        """The inner's ``on_batch_failed``: one call for a batch of
        ``(topic, future)`` whose answer raised (or a transport that
        died under them). Every topic still waiting is answered from the
        trie on its own future and counted as one error fallback and
        one breaker failure, as a per-topic callback would have; one the
        deadline answered already was counted then."""
        for topic, fut in batch:
            if fut.done():
                continue
            probe = self._probe_fut is fut \
                or self._probe_fut is _PROBE_UNBORN
            if probe:
                self._probe_fut = None
            self._record_failure(probe)
            self.error_fallbacks += 1
            self._settle_from_trie(fut, topic, exc)

    # -- the deadline queue ---------------------------------------------

    def _watch(self, fut: asyncio.Future, topic: str, probe: bool) -> None:
        """Put ``fut`` under the per-topic deadline, counted from now.
        The deadline is one constant, so the queue is in due order and
        one timer for its head serves every topic in flight."""
        if self.deadline_ms <= 0:
            return
        loop = asyncio.get_running_loop()
        if self._sweep_loop is not loop:
            # first use, or a new loop: what the old one left is dead
            self._watched.clear()
            self._sweep_timer = None
            self._sweep_loop = loop
        due = loop.time() + self.deadline_ms / 1e3
        self._watched.append((due, fut, topic, probe))
        if self._sweep_timer is None:
            self._arm(loop, due)

    def _arm(self, loop: asyncio.AbstractEventLoop, due: float) -> None:
        self.deadline_timers_armed += 1
        self._sweep_timer = loop.call_at(due, self._sweep, loop, due)

    def _sweep(self, loop: asyncio.AbstractEventLoop, armed: float) -> None:
        """The timer fired: drop every answered head, answer every due
        one from the trie (reason="deadline", one breaker failure each),
        and arm for the first topic that is neither. ``armed`` stands
        for a clock that reads a tick short of what it was set for."""
        if self._sweep_loop is not loop:
            return
        now = max(loop.time(), armed)
        watched = self._watched
        while watched:
            due, fut, topic, probe = watched[0]
            if not fut.done():
                if due > now:
                    self._arm(loop, due)
                    return
                if probe:
                    self._probe_fut = None
                self._record_failure(probe)
                self.deadline_fallbacks += 1
                self._settle_from_trie(fut, topic, None)
            watched.popleft()
        self._sweep_timer = None

    # -- degraded answers on the async surface --------------------------

    def _trie_future(self, topic: str, cause: Exception | None):
        out = asyncio.get_running_loop().create_future()
        self._settle_from_trie(out, topic, cause)
        return out

    def _settle_from_trie(self, out: asyncio.Future, topic: str,
                          cause: Exception | None) -> None:
        tracer = getattr(self.inner, "tracer", None)
        try:
            answer = self._trie(topic)
            if tracer is not None and tracer.sample_n:
                # ADR 015: answered here, by the trie, whatever the
                # inner goes on to do with the batch it rode in
                out._t_done = tracer.clock()
                out._t_via = "fallback"
                out._t_batch = None
            out.set_result(answer)
        except Exception:
            out.set_exception(cause if cause is not None else
                              RuntimeError("matcher degraded and no "
                                           "trie index attached"))

    async def subscribers_async(self, topic: str):
        return await self.enqueue(topic)
