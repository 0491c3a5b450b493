"""From a profiler trace (``.xplane.pb``) to what the benchmark reports:
the seconds in which an operation ran on the device, the operations by
name, and the longest idle gaps. ``jax.profiler.ProfileData`` reads the
file; nothing else is needed.

    python perfbench/xplane.py <file.xplane.pb>     # look at one by hand
"""

from __future__ import annotations

import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import arith  # noqa: E402

DEVICE_PLANE = "/device:TPU:"
# the line of a device plane that carries one event per executed
# operation (the others nest them: modules, steps, annotations)
OP_LINE = "XLA Ops"


def find(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_planes(data) -> list:
    """The planes of whole chips: ``/device:TPU:<n>``, not the planes of
    a chip's parts that some runtimes add beside them."""
    out = []
    for plane in data.planes:
        name = plane.name
        if name.startswith(DEVICE_PLANE) and name[len(DEVICE_PLANE):] \
                .strip().isdigit():
            out.append(plane)
    return out


def op_events(plane) -> list[tuple[str, int, int]]:
    """(name, start_ns, duration_ns) of every operation the chip ran."""
    lines = [ln for ln in plane.lines if ln.name == OP_LINE]
    out = []
    for line in lines:
        for ev in line.events:
            out.append((short_name(ev.name), int(ev.start_ns),
                        int(ev.duration_ns)))
    return out


def short_name(name: str) -> str:
    """An event of the op line is named by its whole HLO instruction,
    ``%tpu_custom_call.4 = u32[128,8]{...} custom-call(...)``: keep the
    instruction's own name."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(path: str, window_s: float, chips: int) -> dict:
    """``busy_s``: union of the operations' intervals, averaged over the
    chips used. ``ops``: name -> (count, seconds) over all chips.
    ``gaps``: the ten longest stretches in which nothing ran on the
    first chip, as (start_ns, seconds)."""
    return reduce_data(load(path), window_s, chips)


def reduce_data(data, window_s: float, chips: int) -> dict:
    planes = device_planes(data)
    ops: dict = {}
    busy = 0.0
    gaps: list = []
    for k, plane in enumerate(planes):
        events = op_events(plane)
        busy += arith.union_seconds((s, d) for _n, s, d in events)
        for name, _s, dur in events:
            c, t = ops.get(name, (0, 0.0))
            ops[name] = (c + 1, t + dur / 1e9)
        if k == 0:
            edge = None
            for _n, s, d in sorted(events, key=lambda e: e[1]):
                if edge is not None and s > edge:
                    gaps.append((edge, (s - edge) / 1e9))
                edge = max(edge or 0, s + d)
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": busy / max(1, chips), "window_s": window_s,
            "planes": [p.name for p in planes], "ops": ops,
            "gaps": gaps[:10]}


def describe(path: str) -> None:
    """Planes, lines and the commonest event names, for a reader."""
    data = load(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names: dict = {}
            n = 0
            for ev in line.events:
                n += 1
                c, t = names.get(ev.name, (0, 0))
                names[ev.name] = (c + 1, t + ev.duration_ns)
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            print(f"  line {line.name!r}: {n} events")
            for name, (c, t) in top:
                print(f"    {c:7d} x {t / 1e6:12.3f} ms  {name[:100]}")


if __name__ == "__main__":
    describe(sys.argv[1])
