"""Micro-batching front end for the device matchers.

The TPU matcher wants large batches (one kernel launch amortized over many
topics); the broker produces one match request per PUBLISH. The MicroBatcher
sits between them: concurrent ``subscribers_async`` calls coalesce for up to
``window_us`` microseconds (or until ``max_batch`` requests are pending) and
go to the device as ONE batch; each caller gets its own SubscriberSet back.

This is the TPU-native replacement for the reference's request-level
concurrency — one goroutine per connection walking a shared locked trie
(vendor/.../v2/server.go:766-793 calling topics.go:484-518 under RWMutex)
becomes data parallelism over a publish micro-batch, per SURVEY §2.3. The
device dispatch runs in a worker thread so the asyncio loop keeps serving
connections while the TPU works — the same overlap the reference gets from
goroutines, without per-publish lock contention.

Under light load a request waits at most ``window_us`` (default 200µs);
single-request batches skip the window entirely when nothing else is queued,
keeping p99 latency competitive with the in-process trie (SURVEY §7 "Latency
vs batching").
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import TYPE_CHECKING

from .supervisor import fail_batch
from .trie import VersionedTopicCache, subs_version

if TYPE_CHECKING:
    from .trie import SubscriberSet


class MicroBatcher:
    """Coalesces concurrent single-topic match requests into device batches.

    ``engine`` is a device engine: ``SigEngine`` or ``ShardedSigEngine``,
    held to the contract ``sig.OverlayedEngine`` states.
    """

    # a trie-bypassed batch never exceeds this many topics: the bypass
    # runs inline on the event loop, and the cap bounds its stall even
    # when the measured estimates say bigger would still win
    BYPASS_CAP = 512
    # every Nth eligible batch goes to the device anyway, so the RTT
    # estimate cannot go stale while the bypass is winning; nor may it
    # stand longer than this many seconds where batches are few (a loop
    # held by wide fan-outs makes four a second: 64 of them are a
    # quarter of a minute, PERF.md section 6, PR 32)
    BYPASS_PROBE_EVERY = 64
    BYPASS_PROBE_SECONDS = 5.0

    def __init__(self, engine, window_us: int = 200,
                 max_batch: int = 256, pipeline_depth: int = 3,
                 cpu_bypass: bool = True) -> None:
        self.engine = engine
        self.window_us = window_us
        self.max_batch = max_batch
        # adaptive low-occupancy CPU bypass; requires engine.index to be
        # the engine's ground truth (true for every real engine — test
        # fakes that return sentinels must disable)
        self.cpu_bypass = cpu_bypass
        # batches allowed in flight at once. On a high-latency link a
        # single serialized batch makes every queued request wait out
        # the full round trip of the one before it; the sig engine's
        # dispatch/collect split lets batch N+1's upload ride the link
        # while batch N decodes.
        self.pipeline_depth = max(1, pipeline_depth)
        self._pending: list[tuple[str, asyncio.Future]] = []
        # the matcher-mode analog of the broker's trie-path match cache:
        # hot topics repeat, and a version-keyed hit skips tokenize +
        # device round trip entirely
        self._cache = VersionedTopicCache()
        self.cache_hits = 0
        self._wakeup: asyncio.Event | None = None
        self._dispatcher: asyncio.Task | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._inflight: asyncio.Semaphore | None = None
        self._collects: set[asyncio.Task] = set()
        self._lock = threading.Lock()
        # adaptive low-occupancy bypass (VERDICT r03 #2): measured
        # device round-trip EWMA vs measured CPU-trie per-topic cost —
        # a batch whose trie cost undercuts half a device round trip is
        # served inline from the trie, so light load sees trie-class
        # latency while bulk load keeps device-class throughput. None
        # until the first post-warm device sample (the compile-laden
        # first round trip must not poison the estimate).
        self._device_rtt: float | None = None
        self._rtt_samples = 0
        # two host-serving cost models, each updated only from its own
        # measured passes (one blended EWMA mispredicted both ways —
        # batch-size mix made it flap): the trie walk is pure
        # per-topic; the sig host path is fixed per-call (ctypes +
        # numpy glue) plus a small per-topic term. Seeds are the
        # 100K-sub measurements; both adapt.
        self._trie_cost = 100e-6          # seed: ~100us/topic
        self._host_fixed = 90e-6          # seed: ~90us/call
        self._host_per = 5e-6             # seed: ~5us/topic
        self._trie_stale = 0              # host-served passes since the
                                          # last trie cost sample
        self._since_probe = 0
        self._probed_at = time.perf_counter()   # the last probe's start
        self._probe_task: asyncio.Task | None = None
        # stats (scraped by the metrics bridge)
        self.batches = 0
        self.batched_topics = 0
        self.largest_batch = 0
        self.bypasses = 0                 # topics served by the bypass
        self.errors = 0                   # batches whose engine call
                                          # raised (ADR 011 observability)
        # ADR 011: callable(batch, exc) told of a batch whose answer
        # raised, once, before its futures are failed; the supervisor
        # sets it and answers them from the CPU trie instead
        self.on_batch_failed = None
        # ADR 015: when the broker's PipelineTracer is attached (see
        # bootstrap.build_matcher) and sampling is on, match futures
        # are stamped with dispatch/done clock marks so the tracer can
        # split coalescing wait from device time, and every micro-batch
        # gets a record (tracer.open_batch) that the engine's host half
        # writes its phases into; off = one attribute check a site
        self.tracer = None
        # the newest dispatch -> fetched result taken on ONE executor
        # thread (the record's device_rtt: whole-batch calls and shadow
        # probes while sampling is on; 0 until then). It contains that
        # thread's waits for the interpreter lock and no loop hop.
        self.device_round_trip = 0.0

    @property
    def device_rtt(self) -> float:
        """EWMA of dispatch -> result as the event loop sees it, executor
        hops included (seconds; 0 until the first post-warm sample): the
        estimate that drives the bypass, scraped by the metrics bridge.
        ``device_round_trip`` is the time taken on the executor thread."""
        return self._device_rtt or 0.0

    def _tracing(self):
        """The tracer while sampling is on, else None."""
        tracer = self.tracer
        return tracer if tracer is not None and tracer.sample_n else None

    # Delegate the sync surface so the batcher is a drop-in matcher.
    def subscribers(self, topic: str) -> "SubscriberSet":
        return self.engine.subscribers(topic)

    def subscribers_batch(self, topics: list[str]) -> "list[SubscriberSet]":
        return self._batch_fn(topics)

    @property
    def _batch_fn(self):
        """The engine's fixed-slot path (fewest bytes/kernels per
        micro-batch): SigEngine's ``subscribers_fixed_batch``. A
        ShardedSigEngine has no method of that name: its
        ``subscribers_batch`` is fixed-slot already."""
        return getattr(self.engine, "subscribers_fixed_batch",
                       self.engine.subscribers_batch)

    def refresh(self, force: bool = False):
        return self.engine.refresh(force=force)

    @property
    def matches(self):
        return self.engine.matches

    @property
    def fallbacks(self):
        return self.engine.fallbacks

    @property
    def index(self):
        return self.engine.index

    @property
    def topic_cache_evictions(self) -> int:
        return self._cache.evictions

    @property
    def topic_cache_size(self) -> int:
        return len(self._cache)

    # ------------------------------------------------------------------

    def enqueue(self, topic: str) -> asyncio.Future:
        """Queue one match WITHOUT awaiting it: returns the future that
        resolves when its micro-batch comes back. The broker's publish
        pipeline uses this to keep hundreds of publishes in flight from
        one connection's read loop — in-flight count, not connection
        count, is what sizes the device batches."""
        loop = asyncio.get_running_loop()
        if self._dispatcher is None or self._loop is not loop:
            self._start(loop)
        fut: asyncio.Future = loop.create_future()
        hit = self._cache.get(topic, self._subs_version())
        if hit is not None:
            self.cache_hits += 1
            tracer = self.tracer
            if tracer is not None and tracer.sample_n:
                fut._t_done = tracer.clock()    # answered here, no batch
                fut._t_via = "cache"
            fut.set_result(hit)
            return fut
        self._pending.append((topic, fut))
        self._wakeup.set()
        return fut

    def _subs_version(self) -> int:
        return subs_version(self.engine.index)

    def _fill_cache(self, version: int, batch, results) -> None:
        for (topic, _), result in zip(batch, results):
            self._cache.put(topic, version, result)

    def _settle(self, version: int, batch, results) -> None:
        """Cache + resolve one batch's futures, stamping the ADR-015
        result-ready mark when tracing is on (the tracer's device span
        ends at result-ready, not at the consumer's in-order await)."""
        tracer = self._tracing()
        if tracer is None:
            self._resolve(version, batch, results, 0)
        else:
            with tracer.section("settle", n=len(batch)):
                self._resolve(version, batch, results, tracer.clock())

    def _resolve(self, version: int, batch, results, done_ns: int) -> None:
        self._fill_cache(version, batch, results)
        for (_, fut), result in zip(batch, results):
            if not fut.done():
                if done_ns:
                    fut._t_done = done_ns
                fut.set_result(result)

    def _fail(self, batch, exc: Exception) -> None:
        """One batch's answer raised: the ADR-011 supervisor, where
        there is one, answers its futures from the CPU trie."""
        self.errors += 1
        fail_batch(self, batch, exc)

    async def subscribers_async(self, topic: str) -> "SubscriberSet":
        """Queue one match; resolves when its micro-batch returns."""
        return await self.enqueue(topic)

    def _start(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop
        self._wakeup = asyncio.Event()
        self._inflight = asyncio.Semaphore(self.pipeline_depth)
        self._dispatcher = loop.create_task(self._run(), name="match-batcher")

    async def close(self) -> None:
        if self._probe_task is not None:
            self._probe_task.cancel()
            try:
                await self._probe_task
            except (asyncio.CancelledError, Exception):
                pass
            self._probe_task = None
        if self._dispatcher is not None:
            self._dispatcher.cancel()
            try:
                await self._dispatcher
            except (asyncio.CancelledError, Exception):
                pass
            self._dispatcher = None
        for task in list(self._collects):
            task.cancel()
        for task in list(self._collects):
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
        self._collects.clear()
        for _, fut in self._pending:
            if not fut.done():
                fut.cancel()
        self._pending.clear()
        # wait out any in-flight background table recompile: tearing the
        # process down mid-compile aborts inside the runtime library
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.close)

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        # pipelined mode needs the dispatch_fixed / collect_fixed split
        # of SigEngine's fixed path; a ShardedSigEngine lacks it and
        # runs one batch at a time through its whole-batch function
        split = (hasattr(self.engine, "dispatch_fixed")
                 and self.pipeline_depth > 1)
        while True:
            await self._wakeup.wait()
            self._wakeup.clear()
            if not self._pending:
                continue
            await self._maybe_window()
            batch, self._pending = (self._pending[:self.max_batch],
                                    self._pending[self.max_batch:])
            if self._pending:
                self._wakeup.set()  # leftovers form the next batch
            topics = [t for t, _ in batch]
            rec = self._note_batch(batch)
            ver = self._subs_version()   # results valid as-of dispatch
            if self._should_bypass(len(batch)):
                self._run_bypass(batch, topics, ver, rec)
            elif split and not self.engine._routes_to_trie():
                # an ADR-008 routed corpus is served by the whole-batch
                # surface (which answers from the engine's trie):
                # dispatch_fixed would force the device round trip the
                # router rejected
                await self._dispatch_pipelined(loop, batch, topics, ver,
                                               rec)
            else:
                await self._run_whole_batch(loop, batch, topics, ver, rec)

    def _note_batch(self, batch):
        """Batch-size counters + the ADR-015 dispatch marks (the
        coalescing-wait span ends for every future in the batch) and
        the batch's record, one object every future points at. Returns
        the record, None with sampling off."""
        self.batches += 1
        self.batched_topics += len(batch)
        self.largest_batch = max(self.largest_batch, len(batch))
        tracer = self._tracing()
        if tracer is None:
            return None
        rec = tracer.open_batch(len(batch))
        for _, fut in batch:
            fut._t_dispatch = rec.t0_ns
            fut._t_batch = rec
        return rec

    async def _maybe_window(self) -> None:
        """Adaptive coalescing window: waiting only pays when the device
        is already busy (arrivals during a flight pile up anyway) AND
        the batch will actually go to the device — when the bypass will
        take it, or nothing is in flight, waiting just adds latency."""
        if (len(self._pending) < self.max_batch and self.window_us > 0
                and not self._should_bypass(len(self._pending))
                and self._inflight._value < self.pipeline_depth):
            await asyncio.sleep(self.window_us / 1e6)

    # -- adaptive CPU bypass -------------------------------------------

    def _host_est(self, n: int) -> float:
        """Predicted cost of serving ``n`` topics via the engine's
        device-free sig path (fixed per-call + per-topic)."""
        return self._host_fixed + n * self._host_per

    def _bypass_cost(self, n: int) -> float:
        """Cheapest host-serving cost for ``n`` topics — the same
        min() _run_bypass takes, so prediction and execution agree."""
        return min(n * self._trie_cost, self._host_est(n))

    def _should_bypass(self, n: int) -> bool:
        """True when serving ``n`` topics inline on the host (trie or
        sig host path, whichever is measured-cheaper) undercuts half a
        device round trip. RTT-estimate refresh rides SHADOW probes
        (background duplicates of bypassed batches), never the caller
        path — a p99 budget of 25ms cannot absorb a periodic full
        round trip."""
        if not self.cpu_bypass or n > self.BYPASS_CAP \
                or self._device_rtt is None:
            return False
        return self._bypass_cost(n) < 0.5 * self._device_rtt

    def _run_bypass(self, batch, topics, ver, rec=None) -> None:
        """Serve one small batch on the host, inline on the loop
        (bounded by BYPASS_CAP x per-topic cost), updating whichever
        cost model served it. The engine's device-free probe path
        (subscribers_host_batch: exact/'+'/'#' signature probes + the
        same C decode) serves when its fixed+per-topic estimate
        undercuts the trie's per-topic one (tiny batches over small
        corpora are the trie's remaining win); else the CPU trie is
        walked."""
        n = len(topics)
        host = self._pick_bypass_host(n)
        if host is None and self.engine.auto_refresh:
            # the trie answers from the live index without touching the
            # engine, which is where staleness is otherwise noticed: a
            # broker whose every batch is this cheap would never
            # recompile its tables after a subscription change
            self.engine.refresh_soon()
        answer = host if host is not None else self._trie_walk
        t0 = time.perf_counter()
        try:
            results = (answer(topics) if rec is None else
                       self._traced_inline(rec, host is not None,
                                           answer, topics))
        except Exception as exc:
            self._fail(batch, exc)
            return
        t1 = time.perf_counter()
        self._update_cost_model(host is not None, n, t1 - t0)
        self._since_probe += 1
        self.bypasses += len(topics)
        self._settle(ver, batch, results)
        if (self._since_probe >= self.BYPASS_PROBE_EVERY
                or t1 - self._probed_at >= self.BYPASS_PROBE_SECONDS):
            self._shadow_probe(topics, rec)

    def _trie_walk(self, topics):
        return [self.engine.index.subscribers(t) for t in topics]

    @staticmethod
    def _traced_inline(rec, via_host: bool, answer, topics):
        """The inline answer of one bypassed batch under its record:
        ``match_host`` is how long it held the loop thread, and the
        ``maxmq.batch`` annotation the same interval in a profiler
        capture (``t0_ns`` carries any ring span over to its clock)."""
        rec.via = "host" if via_host else "trie"
        t0 = rec.tracer.clock()
        try:
            with rec.tracer.section("batch", batch=rec.id, n=rec.n,
                                    via=rec.via, t0_ns=t0):
                return rec.run(answer, topics)
        finally:
            rec.phase("match_host", t0, rec.tracer.clock())

    def _pick_bypass_host(self, n: int):
        """The engine's device-free probe path when its fixed+per-topic
        estimate undercuts the trie's, else None (trie serves). Tiny
        batches periodically re-sample the trie so a winning host path
        cannot let the trie estimate go stale."""
        if n * self._trie_cost < self._host_est(n):
            return None
        if n <= 8 and self._trie_stale >= 64:
            self._trie_stale = 0
            return None
        return self.engine.subscribers_host_batch

    def _update_cost_model(self, via_host: bool, n: int,
                           took: float) -> None:
        """Fold one bypass timing into whichever path served it. The
        host path keeps a two-parameter model: big batches pin the
        per-topic slope, small ones the per-call intercept."""
        if via_host:
            if n >= 16:
                self._host_per += 0.3 * (
                    (took - self._host_fixed) / n - self._host_per)
            else:
                self._host_fixed += 0.3 * (
                    max(took - n * self._host_per, 0.0)
                    - self._host_fixed)
            self._trie_stale += 1
        else:
            self._trie_cost += 0.3 * (took / max(1, n) - self._trie_cost)
            self._trie_stale = 0

    def _shadow_probe(self, topics, of=None) -> None:
        """Duplicate one bypassed batch to the device in the background
        purely to refresh the RTT estimate — no caller waits on it.
        Under tracing it is a batch record of its own (``of`` the batch
        it duplicates), whose phases reach that batch's sampled
        publishes."""
        if self._probe_task is not None and not self._probe_task.done():
            return
        self._since_probe = 0
        self._probed_at = time.perf_counter()
        rec = None
        if of is not None:
            rec = of.tracer.open_batch(len(topics), of=of)
            rec.via = "device"

        async def probe() -> None:
            loop = asyncio.get_running_loop()
            t0 = time.perf_counter()
            try:
                await loop.run_in_executor(
                    None, *self._call(rec, self._batch_fn, list(topics)))
            except Exception:
                return                     # estimate keeps its last value
            took = time.perf_counter() - t0
            if rec is not None:
                self._note_hop(rec)
                rec.tracer.close_shadow(rec)
            self._note_rtt(took)

        self._probe_task = self._loop.create_task(probe())

    @staticmethod
    def _call(rec, fn, *args) -> tuple:
        """What ``run_in_executor`` is to run: ``fn(*args)``, under the
        batch's record when there is one."""
        return (fn, *args) if rec is None else (rec.run, fn, *args)

    def _note_hop(self, rec) -> None:
        """On the loop, first thing after an executor call under ``rec``
        came back: the hop it waited, and the round trip if that call
        took one on its thread."""
        rec.hop()
        rtt_ns = rec.last("device_rtt")
        if rtt_ns:
            self.device_round_trip = rtt_ns / 1e9

    def _note_rtt(self, sample: float) -> None:
        """Record one device round-trip sample (dispatch->collect).
        The first sample carries the XLA compile and is discarded; so
        is one taken while the engine compiles in the background (a
        table rotation holds the interpreter for seconds at a time: on
        a v5e at 1M filters one such sample read 3.1 s, and the bypass
        it talked into winning kept the chip idle long after)."""
        if self.engine.compiling:
            return
        self._rtt_samples += 1
        self._since_probe = 0
        if self._rtt_samples <= 1:
            return
        if self._device_rtt is None:
            self._device_rtt = sample
        else:
            self._device_rtt += 0.3 * (sample - self._device_rtt)

    async def _run_whole_batch(self, loop, batch, topics, ver,
                               rec=None) -> None:
        if rec is not None:
            rec.via = "whole"
        t0 = time.perf_counter()
        try:
            # worker thread: overlap device time with the event loop
            results = await loop.run_in_executor(
                None, *self._call(rec, self._batch_fn, topics))
        except Exception as exc:
            self._fail(batch, exc)
            return
        took = time.perf_counter() - t0
        if rec is not None:
            self._note_hop(rec)
        self._note_rtt(took)
        self._settle(ver, batch, results)

    async def _dispatch_pipelined(self, loop, batch, topics, ver,
                                  rec=None) -> None:
        """Dispatch now, collect in a bounded background task: up to
        ``pipeline_depth`` batches ride the device/link concurrently, so
        a queued request no longer waits out the FULL round trip of the
        batch ahead of it."""
        if rec is not None:
            rec.via = "device"
        await self._inflight.acquire()
        # timestamp AFTER the semaphore: under saturation the wait for a
        # pipeline slot is queueing, not round-trip, and folding it into
        # the RTT EWMA would inflate the bypass threshold
        t0 = time.perf_counter()
        try:
            ctx = await loop.run_in_executor(
                None, *self._call(rec, self.engine.dispatch_fixed, topics))
        except asyncio.CancelledError:
            self._inflight.release()
            self._cancel_futures(batch)
            raise
        except Exception:
            # dispatch refused (device matching disabled for this
            # corpus, resync, table swap): the whole-batch path keeps
            # its CPU-trie fallback semantics — never fail the callers
            # for a condition the engine degrades through
            self._inflight.release()
            await self._run_whole_batch(loop, batch, topics, ver, rec)
            return
        task = loop.create_task(
            self._collect(loop, batch, topics, ctx, ver, t0, rec))
        self._collects.add(task)
        task.add_done_callback(self._collects.discard)

    async def _collect(self, loop, batch, topics, ctx, ver, t0,
                       rec=None) -> None:
        try:
            results = await loop.run_in_executor(
                None, *self._call(rec, self.engine.collect_fixed, topics,
                                  ctx))
        except asyncio.CancelledError:
            self._cancel_futures(batch)
            raise
        except Exception:
            # same degradation contract as dispatch failures
            self.errors += 1
            results = None
        finally:
            self._inflight.release()
        if results is None:
            await self._run_whole_batch(loop, batch, topics, ver, rec)
            return
        took = time.perf_counter() - t0
        if rec is not None:
            self._note_hop(rec)
        self._note_rtt(took)
        self._settle(ver, batch, results)

    @staticmethod
    def _cancel_futures(batch) -> None:
        for _, fut in batch:
            if not fut.done():
                fut.cancel()
