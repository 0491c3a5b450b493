#!/usr/bin/env python3
"""Which host code fills the device's idle time, from one profiler trace.

    python tools/trace_gaps.py <file.xplane.pb> [--json] [--between LO_NS HI_NS]

While ``trace_sample_n > 0`` the broker wraps its synchronous host work
in ``jax.profiler.TraceAnnotation("maxmq.<name>")`` (maxmq_tpu/trace.py,
``host_span``), so a ``jax.profiler`` capture of the broker's process
(``perfbench/run.py --trace 1 --keep-trace FILE``, or an operator's own
``jax.profiler.start_trace``) holds those spans on plane ``/host:CPU``,
one line a thread, on the clock of the device's events. This reads such
a file and prints:

* the device's idle time inside the slice (all of the slice where the
  file has no device plane, as on the CPU);
* for the event loop's thread, and for the other threads together, the
  seconds of that idle time each top-level ``maxmq.*`` name covers, its
  share of the idle time, and what no annotation covers (``maxmq.ack``,
  a subscriber's PUBACK handled inside a chunk's ``maxmq.read``,
  ``maxmq.share`` and ``maxmq.resolve``, the $share picks and the match
  result's pass over the client registry inside a ``maxmq.deliver``,
  ``maxmq.flush``, a burst's ``writev`` inside a flush pass's
  ``maxmq.pass``, and a pass that runs inside another section are cut
  out of the span around them and given rows of their own: self time,
  as the tracer's loop ledger keeps it);
* the ten longest idle gaps, each with the name that covers most of it;
* two checks of the clocks: how many device operations began inside an
  annotated dispatch -> fetch of one batch, and the tracer's clock minus
  the profiler's (from the ``t0_ns`` the batch annotations carry), which
  carries any span of the tracer's ring over to this file's clock.

Every line of the host plane is named ``python``: the loop's thread is
told by the events it carries (``maxmq.read``, ``maxmq.deliver``).
Where the broker timed its selector (a stock asyncio loop, sampling on
at ``serve``) the loop's waits are ``maxmq.idle`` (nothing was ready)
and ``maxmq.poll`` (a ``select`` with ready handles: busy), and
``unannotated`` on the loop's thread is busy time in code no section
names: the ledger's ``other``. Without them it holds the idle time too.

``--json`` also gives each name's whole seconds inside the slice
(``seconds``, not cut to the device's idle time), which is what the
ledger's totals (``report()["loop"]``, ``maxmq_loop_seconds_total``)
are set beside; ``--between`` cuts the slice to two stamps of the
tracer's clock, carried over by the offset above, so that the two are
taken over the same interval.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "perfbench"))

import arith  # noqa: E402
import xplane  # noqa: E402

HOST_PLANE = "/host:CPU"
PREFIX = "maxmq."
LOOP_MARKS = ("maxmq.read", "maxmq.deliver", "maxmq.settle")
# annotations that get a row of their own wherever they nest: their time
# is taken from the span around them
CARVED = ("maxmq.ack", "maxmq.share", "maxmq.resolve", "maxmq.flush",
          "maxmq.pass")


# -- interval arithmetic (nanoseconds; an interval is (start, end)) --------


def merge(intervals) -> list[tuple[int, int]]:
    """The union of the intervals, sorted and disjoint."""
    out: list[tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def complement(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """What of [lo, hi) the merged ``busy`` intervals leave free."""
    out, edge = [], lo
    for b0, b1 in merge(busy):
        if b0 > edge:
            out.append((edge, min(b0, hi)))
        edge = max(edge, b1)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi))
    return [(a, b) for a, b in out if b > a]


def overlap(a, b) -> int:
    """Nanoseconds in both of two sorted, disjoint interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def top_level(events) -> list[tuple[str, int, int]]:
    """The events of one thread that lie inside no other of them."""
    out, edge = [], None
    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        if edge is None or lo >= edge:
            out.append((name, lo, hi))
            edge = hi
    return out


def carve(events) -> list[tuple[str, int, int]]:
    """One thread's self time: its top-level events and every ``CARVED``
    event at any depth, each cut where one of the others lies inside
    it. Anything else that lies inside another event is its parent's."""
    tops = set(top_level(events))
    stack: list[tuple[str, int]] = []       # (name, end) of the open ones
    out, cursor = [], 0

    def close(until: int) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= until:
            name, end = stack.pop()
            out.append((name, cursor, end))
            cursor = end

    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        name, lo, hi = ev
        if name not in CARVED and ev not in tops:
            continue
        close(lo)
        if stack:
            out.append((stack[-1][0], cursor, lo))
        stack.append((name, hi))
        cursor = lo
    close(max((e[2] for e in events), default=0))
    return [(name, lo, hi) for name, lo, hi in out if hi > lo]


def spans_by_name(threads, lo: int = 0, hi: int = 1 << 63) -> dict:
    """``threads``: one list of self-time (name, start, end) a thread.
    Each name's intervals over all of them inside [lo, hi), merged."""
    out: dict = {}
    for events in threads:
        for name, a, b in events:
            out.setdefault(name, []).append((max(a, lo), min(b, hi)))
    return {name: merge(spans) for name, spans in out.items()}


def attribute(idle, names) -> dict:
    """``idle``: sorted disjoint intervals. ``names``: what
    :func:`spans_by_name` gives. Seconds of the idle time each name
    covers, and the seconds no annotation covers."""
    covered = merge(iv for spans in names.values() for iv in spans)
    total = sum(hi - lo for lo, hi in idle)
    return {"names": {name: overlap(idle, spans) / 1e9
                      for name, spans in names.items()},
            "unannotated": (total - overlap(idle, covered)) / 1e9,
            "seconds": {name: sum(hi - lo for lo, hi in spans) / 1e9
                        for name, spans in names.items()}}


def covering(gap, groups) -> str:
    """``group:name`` of the annotation that covers most of ``gap``;
    ``groups``: group -> what :func:`spans_by_name` gives."""
    best, best_ns = "unannotated", 0
    for group, names in groups.items():
        for name, spans in names.items():
            ns = overlap([gap], spans)
            if ns > best_ns:
                best, best_ns = f"{group}:{name}", ns
    return best


# -- the file ---------------------------------------------------------------


def host_threads(data) -> list[list[tuple[str, int, int, dict]]]:
    """The ``maxmq.*`` events of every host thread that has any, with
    their stats."""
    out = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            with warnings.catch_warnings():     # the binding's own, on
                warnings.simplefilter("ignore", DeprecationWarning)  # stats
                events = [(ev.name, int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns), dict(ev.stats))
                          for ev in line.events
                          if ev.name.startswith(PREFIX)]
            if events:
                out.append(events)
    return out


def loop_thread(threads) -> int | None:
    """Index of the thread that carries most of the loop's own events."""
    counts = [sum(1 for e in events if e[0] in LOOP_MARKS)
              for events in threads]
    return counts.index(max(counts)) if counts and max(counts) else None


def round_trips(threads) -> list[tuple[int, int]]:
    """[dispatch start, fetch end) of every batch that has both."""
    starts: dict = {}
    out = []
    for events in threads:
        for name, lo, hi, stats in sorted(events, key=lambda e: e[1]):
            batch = stats.get("batch")
            if name == "maxmq.batch.dispatch":
                starts[batch] = lo
            elif name == "maxmq.batch.fetch" and batch in starts:
                out.append((starts.pop(batch), hi))
    return merge(out)


def analyse(data, between=None) -> dict:
    """``between``: two stamps of the tracer's clock (ns) to cut the
    slice to."""
    threads = host_threads(data)
    planes = xplane.device_planes(data)
    ops = [(s, s + d) for _n, s, d in xplane.op_events(planes[0])] \
        if planes else []
    edges = [t for events in threads for _n, lo, hi, _s in events
             for t in (lo, hi)] + [t for op in ops for t in op]
    if not edges:
        raise SystemExit("no maxmq.* host span and no device operation "
                         "in this trace: was tracing on (trace_sample_n)?")
    lo, hi = min(edges), max(edges)
    offsets = [stats["t0_ns"] - start for events in threads
               for _n, start, _e, stats in events if "t0_ns" in stats]
    offset = int(arith.median(offsets)) if offsets else None
    if between is not None:
        if offset is None:
            raise SystemExit("no annotation here carries the tracer's "
                             "clock (t0_ns): --between has nothing to go by")
        lo, hi = max(lo, between[0] - offset), min(hi, between[1] - offset)
    idle = complement(ops, lo, hi)
    idle_s = sum(b - a for a, b in idle) / 1e9
    k = loop_thread(threads)
    tops = [carve([e[:3] for e in events]) for events in threads]
    groups = {"loop": spans_by_name([tops[k]] if k is not None else [],
                                    lo, hi),
              "other": spans_by_name((t for i, t in enumerate(tops)
                                      if i != k), lo, hi)}
    trips = round_trips(threads)
    gaps = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    return {
        "slice_s": (hi - lo) / 1e9, "device_planes": len(planes),
        "device_busy_s": arith.union_seconds((a, b - a) for a, b in ops),
        "idle_s": idle_s, "host_threads": len(threads),
        "groups": {g: attribute(idle, t) for g, t in groups.items()},
        "gaps": [{"start_s": (a - lo) / 1e9, "seconds": (b - a) / 1e9,
                  "covered_by": covering((a, b), groups)}
                 for a, b in gaps],
        "device_ops": len(ops),
        "device_ops_inside_a_round_trip": sum(
            1 for a, _b in ops if overlap([(a, a + 1)], trips)),
        "tracer_minus_profiler_clock_ns": offset,
    }


def show(out: dict) -> None:
    idle = out["idle_s"]
    print(f"slice {out['slice_s']:.3f} s, device planes "
          f"{out['device_planes']}, device busy {out['device_busy_s']:.6f} s, "
          f"idle {idle:.3f} s; host threads with maxmq.* spans "
          f"{out['host_threads']}")
    for group, title in (("loop", "the loop's thread"),
                         ("other", "the other threads together")):
        got = out["groups"][group]
        print(f"\nidle seconds by top-level annotation, {title}:")
        rows = sorted(got["names"].items(), key=lambda kv: -kv[1])
        for name, secs in rows + [("unannotated", got["unannotated"])]:
            print(f"  {name:28s} {secs:10.4f} s  "
                  f"{100 * secs / idle if idle else 0:6.2f} %")
    print("\nthe longest idle gaps:")
    for gap in out["gaps"]:
        print(f"  at {gap['start_s']:9.4f} s  {gap['seconds']:9.4f} s  "
              f"{gap['covered_by']}")
    print(f"\ndevice operations begun inside an annotated dispatch -> "
          f"fetch: {out['device_ops_inside_a_round_trip']} of "
          f"{out['device_ops']}")
    print("tracer's clock minus this file's clock: "
          f"{out['tracer_minus_profiler_clock_ns']} ns")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a .xplane.pb file")
    ap.add_argument("--json", action="store_true",
                    help="print the numbers as one JSON object")
    ap.add_argument("--between", nargs=2, type=int,
                    metavar=("LO_NS", "HI_NS"),
                    help="cut the slice to two stamps of the tracer's clock")
    args = ap.parse_args()
    out = analyse(xplane.load(args.trace), args.between)
    if args.json:
        print(json.dumps(out))
    else:
        show(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
