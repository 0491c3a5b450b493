"""Batched NFA matcher: JAX/XLA evaluation of the compiled subscription NFA.

One scan step per topic level over the whole batch (the "sequence axis" of
this workload — SURVEY.md section 2.3): the active node set advances through
literal edges (vectorized open-addressing probes) and '+' edges, while
subscriber-carrying nodes emit their *row ids* into the scan output. A
post-scan sort compacts the emitted ids into at most ``max_rows`` matches
per topic; the host unions the rows' entry lists (NFATables.row_entries).

The output is deliberately sparse — matched row ids, not bitmasks: a dense
bitmask over 1M subscriptions is 125KB per publish and HBM-bandwidth-bound,
while matched rows are a few dozen int32s. Static shapes throughout: fixed
batch, fixed max levels, fixed active-set width, fixed max_rows, with
per-topic overflow flags routing rare too-wide/too-deep topics to the exact
CPU trie.

Replaces the reference's lock-guarded recursive walk
(vendor/github.com/mochi-co/mqtt/v2/topics.go:484-518) with a data-parallel
batched evaluation designed for the VPU + HBM model.
"""

from __future__ import annotations

import threading
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from .. import faults
from .nfa import MAX_PROBES, NFATables, compile_trie, hash32
from .topics import pad_topic_batch
from .trie import SubscriberSet, TopicIndex, subs_version

_I32_MAX = np.int32(np.iinfo(np.int32).max)


def match_batch_body(hash_node, hash_tok, hash_val, plus_child, node_mask,
                     hash_mask, toks, lengths, dollar,
                     width: int, table_mask: int, max_rows: int,
                     mesh_axes: tuple = ()):
    """Traceable body of the batched NFA match (no jit wrapper, so the
    sharded matcher in ``parallel/sharded.py`` can re-trace it inside a
    ``shard_map``).

    Args:
      toks: int32[B, Lmax] level-token ids, -1 padded
      lengths: int32[B] level counts (-1 = too deep -> overflow)
      dollar: bool[B] first level begins with '$'
    Returns:
      rows: int32[B, max_rows] matched row ids, ascending, -1 padded
      overflow: bool[B] active set exceeded `width`, topic too deep, or
        matches exceeded `max_rows` (caller falls back to the CPU trie)
    """
    batch, max_levels = toks.shape

    active0 = jnp.full((batch, width), -1, dtype=jnp.int32).at[:, 0].set(0)
    overflow0 = lengths < 0
    if mesh_axes:
        # Under shard_map the scan carry must be typed as device-varying
        # over the mesh axes from step 0 (the step fn mixes in sharded
        # inputs), or the vma checker rejects the scan.
        def vary(x):
            need = tuple(a for a in mesh_axes if a not in jax.typeof(x).vma)
            return jax.lax.pcast(x, need, to="varying") if need else x

        active0, overflow0 = vary(active0), vary(overflow0)

    # Pad the token sequence with one trailing -1 column so the scan runs
    # Lmax+1 steps: step L does the final (exact-depth) emission.
    toks_t = jnp.concatenate(
        [toks, jnp.full((batch, 1), -1, dtype=jnp.int32)], axis=1).T
    level_ids = jnp.arange(max_levels + 1, dtype=jnp.int32)

    def lookup_literal(active, tok):
        """Vectorized (node, token) -> child via bounded linear probing.
        active: [B, W], tok: [B, 1] broadcast over the active set."""
        base = (hash32(active, tok) & jnp.uint32(table_mask)).astype(jnp.int32)
        child = jnp.full_like(active, -1)
        for p in range(MAX_PROBES):
            slot = (base + p) & table_mask
            hit = (hash_node[slot] == active) & (hash_tok[slot] == tok)
            child = jnp.where((child < 0) & hit, hash_val[slot], child)
        return child

    def step(carry, inputs):
        active, overflow = carry
        tok, level = inputs                    # tok: [B], level: scalar
        valid = active >= 0                    # [B, W]
        not_done = level < lengths             # topic still has levels
        at_end = level == lengths              # exact depth reached
        # [MQTT-4.7.2-1]: '$'-topics never match root-level wildcards
        wild_ok = ~(dollar & (level == 0))     # [B]

        # '#'-terminal emission: matches at every prefix depth incl. parent
        emit_hash = (not_done | at_end) & wild_ok
        hash_rows = jnp.where(
            valid & emit_hash[:, None],
            hash_mask[jnp.maximum(active, 0)], -1)
        self_rows = jnp.where(
            valid & at_end[:, None],
            node_mask[jnp.maximum(active, 0)], -1)
        rows = jnp.concatenate([hash_rows, self_rows], axis=1)  # [B, 2W]

        # transitions (only for topics that still have levels)
        lit = lookup_literal(jnp.maximum(active, 0), tok[:, None])
        lit = jnp.where(valid & not_done[:, None], lit, -1)
        plus = plus_child[jnp.maximum(active, 0)]
        plus = jnp.where(valid & (not_done & wild_ok)[:, None], plus, -1)
        cand = jnp.concatenate([lit, plus], axis=1)     # [B, 2W]

        n_valid = jnp.sum((cand >= 0).astype(jnp.int32), axis=1)
        overflow = overflow | (n_valid > width)
        order = jnp.argsort(jnp.where(cand >= 0, 0, 1), axis=1, stable=True)
        packed = jnp.take_along_axis(cand, order, axis=1)[:, :width]
        active = jnp.where(not_done[:, None], packed, active)
        return (active, overflow), rows

    (_active, overflow), emitted = jax.lax.scan(
        step, (active0, overflow0), (toks_t, level_ids))

    # emitted: [L+1, B, 2W] row ids (-1 = none). Compact per topic: sort
    # ascending with -1 mapped to +inf, keep the first max_rows.
    emitted = jnp.moveaxis(emitted, 0, 1).reshape(batch, -1)
    emitted = jnp.where(emitted < 0, _I32_MAX, emitted)
    emitted = jax.lax.sort(emitted, dimension=1)
    n_matched = jnp.sum((emitted != _I32_MAX).astype(jnp.int32), axis=1)
    overflow = overflow | (n_matched > max_rows)
    rows = emitted[:, :max_rows]
    rows = jnp.where(rows == _I32_MAX, -1, rows)
    return rows, overflow


match_batch_device = partial(
    jax.jit,
    static_argnames=("width", "table_mask", "max_rows", "mesh_axes"))(
    match_batch_body)


class NFAEngine:
    """Device-resident matcher bound to a TopicIndex.

    Compiles the trie into NFA tables, keeps them on the target device
    (double-buffered: a publish sees either the old or new table, never a
    torn one — the atomic swap the Go code gets from its root mutex), and
    answers ``subscribers()`` with exact SubscriberSet semantics, falling
    back to the CPU trie for overflow topics.
    """

    def __init__(self, index: TopicIndex, width: int = 32,
                 max_levels: int = 16, max_rows: int = 128, device=None,
                 auto_refresh: bool = True) -> None:
        self.index = index
        self.width = width
        self.max_levels = max_levels
        self.max_rows = max_rows
        self.device = device
        self.auto_refresh = auto_refresh
        self._lock = threading.Lock()
        self._tables: NFATables | None = None
        self._device_tables = None
        self.fallbacks = 0
        self.matches = 0
        self.refresh(force=True)

    # ------------------------------------------------------------------

    def refresh(self, force: bool = False) -> bool:
        """Recompile + upload if the index changed. Cheap no-op otherwise."""
        if (not force and self._tables is not None
                and self._tables.version == subs_version(self.index)):
            return False
        faults.fire(faults.DEVICE_RECOMPILE)
        tables = compile_trie(self.index)
        arrays = (tables.hash_node, tables.hash_tok, tables.hash_val,
                  tables.plus_child, tables.node_mask, tables.hash_mask)
        dev = [jax.device_put(a, self.device) for a in arrays]
        with self._lock:
            self._tables = tables
            self._device_tables = dev
        return True

    @property
    def tables(self) -> NFATables:
        return self._tables

    # ------------------------------------------------------------------

    def match_raw(self, topics: list[str]):
        """Device match of a topic batch. Returns (rows int32[B, max_rows],
        overflow bool[B], tables) — the tables the batch actually ran on."""
        if self.auto_refresh:
            self.refresh()
        faults.fire(faults.DEVICE_MATCH)
        with self._lock:
            tables = self._tables
            dev = self._device_tables
        toks, lengths, dollar = tables.tokenize(topics, self.max_levels)
        # bucket the batch axis: one XLA compile per ladder shape, not
        # per distinct micro-batch size; per-topic outputs trim clean
        b = len(topics)
        toks, lengths, dollar = pad_topic_batch(toks, lengths, dollar)
        rows, overflow = match_batch_device(
            *dev, jnp.asarray(toks), jnp.asarray(lengths),
            jnp.asarray(dollar), width=self.width,
            table_mask=tables.table_size - 1, max_rows=self.max_rows)
        return np.asarray(rows)[:b], np.asarray(overflow)[:b], tables

    def subscribers_batch(self, topics: list[str]) -> list[SubscriberSet]:
        rows, overflow, tables = self.match_raw(topics)
        out = []
        for i, topic in enumerate(topics):
            self.matches += 1
            if overflow[i]:
                self.fallbacks += 1
                out.append(self.index.subscribers(topic))
            else:
                out.append(self.decode(rows[i], tables))
        return out

    def subscribers(self, topic: str) -> SubscriberSet:
        """Single-topic match (the broker's pluggable-matcher entry point)."""
        return self.subscribers_batch([topic])[0]

    async def subscribers_async(self, topic: str) -> SubscriberSet:
        """Event-loop-friendly match: recompiles (O(subs) Python + possible
        XLA retrace) and matches in a worker thread so the broker's asyncio
        loop never stalls behind the table swap."""
        import asyncio

        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self.subscribers, topic)

    @staticmethod
    def decode(row_ids: np.ndarray, tables: NFATables,
               into: SubscriberSet | None = None) -> SubscriberSet:
        """Union the matched rows' entry lists into an exact SubscriberSet."""
        result = SubscriberSet() if into is None else into
        entries = tables.entries
        row_entries = tables.row_entries
        for r in row_ids:
            if r < 0:
                break  # -1 padding is sorted to the tail
            for b in row_entries[r]:
                entry = entries[b]
                if entry.shared:
                    for cid, sub in entry.candidates.items():
                        result.add_shared(entry.group, sub.filter, cid, sub)
                else:
                    sub = entry.subscription
                    result.add(entry.client_id, sub, sub.filter)
        return result
