"""What the fused match kernel must move, and the chip's peaks.

The kernel (``maxmq_tpu/matching/sig_pallas.py``) is one ``pallas_call``
per word chunk. A call reads its batch tile's split signatures and flag,
its chunk's expansion constant and bit planes (block index constant over
the grid: fetched once), and writes ``1 + max_rows`` words a topic.
Nothing else has to cross HBM; the compare itself is integer VPU work,
for which there is no public peak, so the only roofline stated here is
the bandwidth one. Where a size is not known per event (the trace does
not say which batch bucket an event served) the smallest is taken, so
the bytes, and with them the share, are a floor.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
# sig_pallas.SELECT_EXPAND_MAX: at or under this many groups the
# expansion constant is a [1, chunk] group-of-word row, above it a
# [g_pad, chunk] one-hot
SELECT_EXPAND_MAX = 40
SMALLEST_BUCKET = 16        # rows of the smallest served batch program


def peak(device_kind: str, what: str) -> float:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "perfbench/peaks.json")
    return float(table[device_kind][what])


def chunk_call_bytes(g_pad: int, chunk: int, plane_rows: int, groups: int,
                     rows: int, max_rows: int) -> int:
    """Bytes one chunk's ``pallas_call`` must move for ``rows`` topics."""
    select = groups <= SELECT_EXPAND_MAX
    expand = 4 * chunk * (1 if select else g_pad)
    planes = 4 * chunk * plane_rows
    per_row = 4 * ((g_pad if select else 2 * g_pad) + 1 + 1 + max_rows)
    return expand + planes + rows * per_row


def kernel_call_bytes(kernel: dict) -> float:
    """Mean bytes per chunk call over one batch's calls. ``kernel``:
    the engine's ``kernel_plan`` under "plan", and "max_rows"."""
    plan, max_rows = kernel["plan"], kernel["max_rows"]
    groups = plan["groups32"] + plan["groups16"]
    total = 0
    for n, chunk, plane_rows in ((plan["n_chunks32"], plan["chunk32"], 32),
                                 (plan["n_chunks16"], plan["chunk16"], 16)):
        total += n * chunk_call_bytes(plan["g_pad"], chunk, plane_rows,
                                      groups, SMALLEST_BUCKET, max_rows)
    return total / max(1, plan["n_chunks"])
