"""Signature matcher: wildcard matching as grouped hash-equality — the
bandwidth-optimal TPU formulation.

A walk of the subscription trie on the device is O(B x total-trie-slots)
with a [B, S] state per level, and the per-level parent gather dominates.
This module has no walk, by observing that every MQTT filter is an
*exact match in disguise*:

* a filter with no '#' and '+' at positions P matches topic T iff
  ``depth(T) == depth(F)`` and ``T[i] == F[i]`` for every literal position
  ``i not in P``;
* a filter ``l0/../l(p-1)/#`` matches iff ``depth(T) >= p`` and the first
  p levels match the same way (the >= includes the parent-match rule
  [MQTT-4.7.1.2]).

So filters are grouped by *shape* — (has-'#', depth-or-prefix-len, set of
literal positions) — and within a group, matching is equality of a single
uint32 signature: a random-odd-multiplier linear hash of the literal-level
token ids (+ the depth for exact groups). On device, per topic, ONE
signature per group is computed (a tiny [B, G] int op), then compared
against every row's stored signature — a pure broadcast compare bit-packed
straight into uint32 match words. No gathers, no per-level state, no MXU
dependence; the data flow is the shape the VPU and HBM like best. Real
corpora produce tens-to-hundreds of groups (a 100K-filter IoT mix: ~130).

Collisions cannot corrupt results: the host decode re-verifies every
candidate row with ``topics.filter_matches_topic`` (an O(levels) exact
check), so a hash collision costs one wasted candidate, never a wrong
delivery.

Rows are padded per group to a multiple of 32 so each group packs its own
words independently — the concatenated [B, W] word matrix is the only
materialized intermediate (32x smaller than the [B, R] bool matrix).

Semantics parity surface: vendor/github.com/mochi-co/mqtt/v2/
topics.go:484-555 (`Subscribers`/`scanSubscribers`).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from .. import faults
from ..protocol.packets import Subscription
from ..trace import NO_SPAN, active_batch, host_span
from .topics import (batch_bucket as _batch_bucket, filter_matches_topic,
                     intern_level, split_levels, tokenize_cached,
                     tokenize_topics)
from .trie import SubscriberSet, TopicIndex, merge_subscription

MAX_GROUPS = 4096   # compile guard: pathological corpora fall back (engine)
DEPTH_CAP = 63      # deepest literal level any compiled group may inspect
                    # (the compact tokenizer's int8 length encoding bound)


@dataclass
class Entry:
    """One subscriber bit: an ordinary (client, sub) or a shared pair."""

    client_id: str = ""
    subscription: Subscription | None = None
    group: str = ""          # non-empty => shared pair
    filter: str = ""
    # shared pairs carry the full candidate map
    candidates: dict[str, Subscription] = field(default_factory=dict)

    @property
    def shared(self) -> bool:
        return bool(self.group)


class EntryBuilder:
    """Accumulates Entry records with `$share` (group, filter) dedup: the
    subscriber-bit construction of the compiled row tables, so every
    member of a shared group rides ONE row bit and merge semantics have
    one definition."""

    def __init__(self) -> None:
        self.entries: list[Entry] = []
        self._shared: dict[tuple[str, str], int] = {}

    def add(self, filt: str, client_id: str, sub: Subscription,
            group: str) -> int | None:
        """Record one subscription. Returns the bit index to place on the
        row, or None when this shared (group, filter) pair already has
        its bit placed (the new member only joins the candidate map)."""
        if group:
            key = (group, sub.filter)
            bit = self._shared.get(key)
            if bit is not None:
                self.entries[bit].candidates[client_id] = sub
                return None
            bit = len(self.entries)
            self._shared[key] = bit
            entry = Entry(group=group, filter=sub.filter)
            entry.candidates[client_id] = sub
            self.entries.append(entry)
            return bit
        bit = len(self.entries)
        self.entries.append(Entry(client_id=client_id, subscription=sub,
                                  filter=filt))
        return bit


def _group_constants(key: tuple[bool, int, tuple[int, ...]],
                     size: int) -> np.ndarray:
    """Deterministic (process-independent) random odd uint32 multipliers for
    one group shape: the first len(kept) are per-level coefficients, the
    last is the exact-group depth coefficient."""
    rng = np.random.default_rng((0x5EED, int(key[0]), key[1], *key[2]))
    c = rng.integers(0, 1 << 32, size=size, dtype=np.uint32)
    return c | np.uint32(1)


W16_MAX_GROUP_ROWS = 512  # beyond this a collision-free 16-bit image is
                          # birthday-improbable (p_fail/try ~ 1-e^(-n^2/2^17))
                          # and the false-candidate rate (rows/2^16 per
                          # topic) stops being noise
_W16_FOLD_TRIES = 8
_W16_PAD = np.uint16(0xFFFF)    # pad-row poison in the 16-bit planes


def _fold16(sig: np.ndarray, mult) -> np.ndarray:
    """Multiply-shift fold of uint32 signatures to 16 bits. The topic
    side computes the same (sig * mult) >> 16 on device, so fold
    equality is exactly plane equality; a topic-vs-row fold collision
    is a wasted (host-verified) candidate, never a wrong delivery."""
    with np.errstate(over="ignore"):
        return ((sig * np.uint32(mult)) >> np.uint32(16)).astype(np.uint16)


def _pick_fold16(g: "GroupSpec", sigs: np.ndarray):
    """(mult, sig16) for a group whose signatures fit 16 bits: an odd
    multiply-shift fold that is injective on the group's row signatures
    (one word then still holds at most one true match, preserving the
    kernel's single-bit extraction invariant) and avoids the 0xFFFF
    pad poison — or None (the group keeps 32-bit planes)."""
    if not 0 < len(sigs) <= W16_MAX_GROUP_ROWS:
        return None
    rng = np.random.default_rng((0x16B1, int(g.is_hash), g.depth,
                                 *g.kept))
    for m in rng.integers(0, 1 << 32, size=_W16_FOLD_TRIES,
                          dtype=np.uint32):
        m = int(m) | 1
        f = _fold16(sigs, m)
        if (f != _W16_PAD).all() and len(np.unique(f)) == len(f):
            return m, f
    return None


@dataclass
class GroupSpec:
    """One wildcard shape: every filter in it matches by signature equality."""

    is_hash: bool            # trailing '#'
    depth: int               # exact depth, or '#'-prefix length
    kept: tuple[int, ...]    # literal (non-'+') level positions
    coef: np.ndarray         # uint32[len(kept)] per-position multipliers
    depth_coef: int          # uint32 multiplier on depth (0 for '#' groups)
    wild_first: bool         # level 0 is a wildcard => '$'-topic exclusion
    rows: list[int] = None   # row ids (padded layout), filled by compiler

    def signature(self, toks: np.ndarray) -> np.ndarray:
        """Host-side signature of token rows [N, >=depth] (uint32 wrap)."""
        sig = np.zeros(toks.shape[0], dtype=np.uint32)
        with np.errstate(over="ignore"):
            for c, pos in zip(self.coef, self.kept):
                sig += c * toks[:, pos].astype(np.uint32)
            if not self.is_hash:
                sig += np.uint32(self.depth_coef) * np.uint32(self.depth)
        return sig


@dataclass
class HostExactGroup:
    """Full-exact filters of one depth (no wildcards): a topic of depth d
    can match at most this one group, so matching is ONE vectorized
    searchsorted on host — no reason to spend device table width on it.
    (The reference trie spends its whole walk on exactly these; here they
    cost one binary search and the device handles only the combinatorial
    wildcard rows.)"""

    depth: int
    spec: GroupSpec
    sigs: np.ndarray       # uint32[n] SORTED signatures
    rows: np.ndarray       # int32[n] row ids aligned with sigs


@dataclass
class HostPlusProbe:
    """All '+'-shape groups of one exact depth, vectorized for the host
    probe. A '+' filter (no trailing '#') is still an *exact-equality*
    match — fixed depth, fixed literal positions — so each group costs
    one hashed signature + one binary search per topic, the host's
    natural strength. The device keeps only the '#'-prefix groups, whose
    per-topic candidate count is genuinely combinatorial; this split cuts
    device compare work ~4x on IoT corpora and is the transfer-optimal
    boundary (candidates per topic, not rows, cross the link)."""

    depth: int
    coef: np.ndarray       # uint32[K, depth] multipliers (0 at '+' slots)
    dc: np.ndarray         # uint32[K] depth-term addends (dc * depth)
    wildf: np.ndarray      # bool[K] level-0 is '+': '$'-topic exclusion
    sigs: list             # K SORTED uint32 signature arrays
    rows: list             # K int32 row-id arrays aligned with sigs


@dataclass
class SigTables:
    """Compiled signature matcher + host-side decode tables."""

    groups: list[GroupSpec]
    # device-ready constants (host numpy; engine device_puts them)
    topo_coef: np.ndarray     # uint32[G, Lmax] per-level multipliers (0=off)
    depth_coef: np.ndarray    # uint32[G] depth multipliers (0 for '#')
    min_depth: np.ndarray     # int32[G] required depth ('#': >=, exact: ==)
    is_hash: np.ndarray       # bool[G]
    wild_first: np.ndarray    # bool[G]
    row_sig: np.ndarray       # uint32[R_padded] per-row signatures
    group_words: np.ndarray   # int32[G] word count per group (R_g/32)
    row_entries: list[tuple[int, ...]]    # row id -> entry indices
    row_levels: list[tuple[str, ...] | None]  # row id -> filter levels
    entries: list[Entry]
    vocab: dict[str, int]
    n_rows: int               # padded DEVICE row count (== 32 * words);
                              # host-probed rows use ids >= n_rows
    max_depth: int            # deepest literal position device groups read
    host_exact: dict[int, HostExactGroup] = None   # depth -> group
    version: int = -1
    host_plus: dict = None    # depth -> HostPlusProbe ('+'-shape groups)
    host_hash: dict = None    # depth -> HostPlusProbe over the DEVICE
                              # '#'-groups (sorted views of the same
                              # rows) — the device-free probe path
    probe_depth: int = 0      # deepest literal position ANY group reads
                              # (device or host_plus) = tokenizer window
    # dual-width planes: groups whose signatures admit an injective
    # 16-bit multiply-shift fold get packed 16-bit plane tables (two
    # rows per uint32 word — half the compare passes and half the
    # constant traffic in the fused kernel); the rest keep 32-bit
    # planes. Groups are laid out 32-bit-first so each width is
    # contiguous in word space (sig_pallas chunks stay single-width).
    group_w16: np.ndarray = None   # bool[G] 16-bit-plane-eligible
    fold_mult: np.ndarray = None   # uint32[G] odd fold mults (0 = 32-bit)
    row_sig16: np.ndarray = None   # uint16[R_padded] folded row sigs
                                   # (0xFFFF pad poison; 0 for 32-bit
                                   # groups' rows — never compared)

    def tokenize(self, topics: list[str], max_levels: int):
        return tokenize_cached(self, topics, max_levels)


def compile_sig(index, version: int | None = None,
                vocab: dict[str, int] | None = None,
                max_levels: int = 16) -> SigTables:
    if version is None:
        from .trie import subs_version
        version = subs_version(index)
    return compile_sig_subscriptions(index.all_subscriptions(), version,
                                     vocab=vocab, max_levels=max_levels)


def compile_sig_subscriptions(subs, version: int = 0,  # qa: complex
                              vocab: dict[str, int] | None = None,
                              max_levels: int = 16) -> SigTables:
    """Build signature tables from a subscription snapshot: the
    ``(trie path, client_id, subscription, group)`` tuples of
    ``TopicIndex.all_subscriptions()``."""
    builder = EntryBuilder()
    if vocab is None:
        vocab = {}

    # one row per unique filter path; group rows by wildcard shape
    filt_row: dict[str, int] = {}
    row_bits: list[list[int]] = []
    row_filt: list[tuple[str, ...]] = []
    for filt, client_id, sub, group in subs:
        # `filt` is the trie path: already '$share'-stripped for shared subs
        bit = builder.add(filt, client_id, sub, group)
        r = filt_row.get(filt)
        if r is None:
            r = filt_row[filt] = len(row_bits)
            row_bits.append([])
            row_filt.append(tuple(split_levels(filt)))
        if bit is not None:
            row_bits[r].append(bit)

    group_map: dict[tuple, GroupSpec] = {}
    group_rows: dict[tuple, list[int]] = {}
    deep_rows: list[int] = []    # filters beyond the depth cap: CPU-only
    for r, levels in enumerate(row_filt):
        is_hash = bool(levels) and levels[-1] == "#"
        lits = levels[:-1] if is_hash else levels
        depth = len(lits)
        if depth > DEPTH_CAP:
            # such filters only match topics deeper than DEPTH_CAP, which
            # every tokenizer flags as overflow -> CPU fallback covers them
            # (the word path additionally overflows anything beyond its
            # max_levels window, so depths in (max_levels, DEPTH_CAP] are
            # safe there too)
            deep_rows.append(r)
            continue
        kept = tuple(i for i, lv in enumerate(lits) if lv != "+")
        for i in kept:
            intern_level(vocab, lits[i])
        key = (is_hash, depth, kept)
        spec = group_map.get(key)
        if spec is None:
            coef = _group_constants(key, len(kept) + 1)
            spec = GroupSpec(
                is_hash=is_hash, depth=depth, kept=kept,
                coef=coef[:-1], depth_coef=0 if is_hash else int(coef[-1]),
                wild_first=(depth == 0 and is_hash) or
                           (depth > 0 and 0 not in kept))
            group_map[key] = spec
            group_rows[key] = []
        group_rows[key].append(r)

    # exact-shape groups (no trailing '#') leave the device: every one is
    # an equality probe — full-literal groups via the per-depth esig
    # searchsorted (HostExactGroup, one group can exist per depth), '+'
    # groups via the per-(depth, shape) probe (HostPlusProbe). The device
    # keeps only '#'-prefix groups, the combinatorial wildcard dimension.
    exact_keys = [k for k, g in group_map.items()
                  if not g.is_hash and len(g.kept) == g.depth]
    host_specs = {k: group_map.pop(k) for k in exact_keys}
    host_rows = {k: group_rows.pop(k) for k in exact_keys}
    plus_keys = [k for k, g in group_map.items() if not g.is_hash]
    plus_specs = {k: group_map.pop(k) for k in plus_keys}
    plus_rows = {k: group_rows.pop(k) for k in plus_keys}

    # per-group signatures first: 16-bit plane eligibility needs them
    # BEFORE the padded layout is fixed, because eligible groups are
    # laid out after the 32-bit ones (contiguous word regions per width)
    staged = []
    for key, g in group_map.items():
        rows = group_rows[key]
        toks = np.zeros((len(rows), max(g.depth, 1)), dtype=np.int32)
        for j, r in enumerate(rows):
            levels = row_filt[r]
            lits = levels[:-1] if g.is_hash else levels
            for pos in g.kept:
                toks[j, pos] = vocab[lits[pos]]
        s = g.signature(toks)
        staged.append((g, rows, s, _pick_fold16(g, s)))
    # stable sort: 32-bit groups first, then the 16-bit-eligible ones
    staged.sort(key=lambda t: t[3] is not None)
    groups = [t[0] for t in staged]

    # padded row layout: groups contiguous, each padded to a multiple of 32
    max_depth = max((g.depth for g in groups), default=0)
    topo_coef = np.zeros((len(groups), max(max_depth, 1)), dtype=np.uint32)
    depth_coef = np.zeros(len(groups), dtype=np.uint32)
    min_depth = np.zeros(len(groups), dtype=np.int32)
    is_hash_a = np.zeros(len(groups), dtype=bool)
    wild_first = np.zeros(len(groups), dtype=bool)
    group_words = np.zeros(len(groups), dtype=np.int32)
    group_w16 = np.zeros(len(groups), dtype=bool)
    fold_mult = np.zeros(len(groups), dtype=np.uint32)

    row_entries: list[tuple[int, ...]] = []
    row_levels: list[tuple[str, ...] | None] = []
    sigs: list[np.ndarray] = []
    sigs16: list[np.ndarray] = []
    hash_sig_list: list[tuple[GroupSpec, np.ndarray]] = []
    for gi, (g, rows, s, fold) in enumerate(staged):
        for c, pos in zip(g.coef, g.kept):
            topo_coef[gi, pos] = c
        depth_coef[gi] = g.depth_coef
        min_depth[gi] = g.depth
        is_hash_a[gi] = g.is_hash
        wild_first[gi] = g.wild_first
        n_pad = (-len(rows)) % 32
        group_words[gi] = (len(rows) + n_pad) // 32
        for r in rows:
            row_entries.append(tuple(row_bits[r]))
            row_levels.append(row_filt[r])
        g.rows = list(range(len(row_entries) - len(rows),
                            len(row_entries)))
        hash_sig_list.append((g, s))
        # padding rows get a poison signature: an all-zero pad sig would
        # match any topic whose (adjusted) signature is 0 and flood the
        # match stream; 0xFFFFFFFF collides only at the 2^-32 baseline rate
        # (and collisions are verified away on host regardless)
        sigs.append(np.concatenate(
            [s, np.full(n_pad, 0xFFFFFFFF, dtype=np.uint32)]))
        if fold is not None:
            group_w16[gi] = True
            fold_mult[gi] = fold[0]
            s16 = fold[1]
        else:
            s16 = np.zeros(len(rows), dtype=np.uint16)
        sigs16.append(np.concatenate(
            [s16, np.full(n_pad, _W16_PAD, dtype=np.uint16)]))
        row_entries.extend(() for _ in range(n_pad))
        row_levels.extend(None for _ in range(n_pad))

    row_sig = (np.concatenate(sigs) if sigs
               else np.zeros(0, dtype=np.uint32))
    row_sig16 = (np.concatenate(sigs16) if sigs16
                 else np.zeros(0, dtype=np.uint16))
    n_device_rows = len(row_entries)

    host_exact: dict[int, HostExactGroup] = {}
    for key, spec in host_specs.items():
        rows = host_rows[key]
        d = spec.depth
        toks = np.zeros((len(rows), max(d, 1)), dtype=np.int32)
        ids = np.empty(len(rows), dtype=np.int32)
        for j, r in enumerate(rows):
            levels = row_filt[r]
            for pos in range(d):
                toks[j, pos] = vocab[levels[pos]]
            ids[j] = len(row_entries)
            row_entries.append(tuple(row_bits[r]))
            row_levels.append(levels)
        s = spec.signature(toks)
        order = np.argsort(s, kind="stable")
        host_exact[d] = HostExactGroup(depth=d, spec=spec,
                                       sigs=s[order], rows=ids[order])

    by_depth: dict[int, list] = {}
    for key, spec in plus_specs.items():
        by_depth.setdefault(spec.depth, []).append((spec, plus_rows[key]))
    host_plus: dict[int, HostPlusProbe] = {}
    for d, entries_d in by_depth.items():
        k_n = len(entries_d)
        coef = np.zeros((k_n, max(d, 1)), dtype=np.uint32)
        dc = np.zeros(k_n, dtype=np.uint32)
        wildf = np.zeros(k_n, dtype=bool)
        sig_arrs, row_arrs = [], []
        for k, (spec, rows) in enumerate(entries_d):
            for c, pos in zip(spec.coef, spec.kept):
                coef[k, pos] = c
            with np.errstate(over="ignore"):
                dc[k] = np.uint32(spec.depth_coef) * np.uint32(d)
            wildf[k] = spec.wild_first
            toks = np.zeros((len(rows), max(d, 1)), dtype=np.int32)
            ids = np.empty(len(rows), dtype=np.int32)
            for j, r in enumerate(rows):
                levels = row_filt[r]
                for pos in spec.kept:
                    toks[j, pos] = vocab[levels[pos]]
                ids[j] = len(row_entries)
                row_entries.append(tuple(row_bits[r]))
                row_levels.append(levels)
            s = spec.signature(toks)
            order = np.argsort(s, kind="stable")
            sig_arrs.append(s[order])
            row_arrs.append(ids[order])
        host_plus[d] = HostPlusProbe(depth=d, coef=coef, dc=dc, wildf=wildf,
                                     sigs=sig_arrs, rows=row_arrs)

    # Sorted host views of the device '#'-groups (same rows, same
    # signatures — just argsorted): the device-free probe path
    # (host_hash_rows) used by the batcher's low-occupancy bypass, where
    # a handful of binary searches beats a device round trip. dc=0
    # (hash groups carry no depth term); applicability is depth >= d.
    hash_by_depth: dict[int, list] = {}
    for g, s in hash_sig_list:
        hash_by_depth.setdefault(g.depth, []).append((g, s))
    host_hash: dict[int, HostPlusProbe] = {}
    for d, entries_d in hash_by_depth.items():
        k_n = len(entries_d)
        coef = np.zeros((k_n, max(d, 1)), dtype=np.uint32)
        dc = np.zeros(k_n, dtype=np.uint32)
        wildf = np.zeros(k_n, dtype=bool)
        sig_arrs, row_arrs = [], []
        for k, (g, s) in enumerate(entries_d):
            for c, pos in zip(g.coef, g.kept):
                coef[k, pos] = c
            wildf[k] = g.wild_first
            ids = np.asarray(g.rows, dtype=np.int32)
            order = np.argsort(s, kind="stable")
            sig_arrs.append(s[order])
            row_arrs.append(ids[order])
        host_hash[d] = HostPlusProbe(depth=d, coef=coef, dc=dc,
                                     wildf=wildf, sigs=sig_arrs,
                                     rows=row_arrs)

    # deep filters (beyond max_levels) only match topics the tokenizer
    # flags as overflow; they live in rows past the device region too so
    # decode can still resolve them after a CPU fallback
    tables = SigTables(
        groups=groups, topo_coef=topo_coef, depth_coef=depth_coef,
        min_depth=min_depth, is_hash=is_hash_a, wild_first=wild_first,
        row_sig=row_sig, group_words=group_words,
        group_w16=group_w16, fold_mult=fold_mult, row_sig16=row_sig16,
        row_entries=row_entries, row_levels=row_levels,
        entries=builder.entries, vocab=vocab, n_rows=n_device_rows,
        max_depth=max_depth, host_exact=host_exact, version=version,
        host_plus=host_plus, host_hash=host_hash,
        # the tokenizer window must cover every literal position any
        # probe reads: device '#' prefixes, '+' shapes AND full-exact
        # depths (the unified native probe reads the narrow window)
        probe_depth=max([max_depth] + [d for d in host_plus]
                        + [d for d in host_exact]))
    tables.deep_rows = deep_rows
    return tables


def exact_sigs(host_exact: dict, toks32: np.ndarray,
               lengths: np.ndarray) -> np.ndarray:
    """uint32[B] exact-group signature per topic (0 where the topic's
    depth has no full-exact group — callers mask by depth, not by 0).
    The numpy twin of the C++ tokenizer's esig output."""
    sigs = np.zeros(len(lengths), dtype=np.uint32)
    for d, g in (host_exact or {}).items():
        sel = np.nonzero(lengths == d)[0]
        if sel.size:
            sigs[sel] = g.spec.signature(toks32[sel])
    return sigs


def host_exact_rows(tables: SigTables, toks32: np.ndarray,
                    lengths: np.ndarray) -> list[np.ndarray]:
    """Vectorized host half of the match: for each topic, the candidate
    rows among full-exact filters (one searchsorted per exact-depth group;
    collisions verified in decode like every other candidate)."""
    sigs = exact_sigs(tables.host_exact, toks32, lengths)
    return host_exact_rows_from_sig(tables, sigs, lengths)


def _scatter_hits(out: list, ti_parts: list, row_parts: list) -> list:
    """Distribute (topic-id, row-id) hit pairs into the per-topic list
    with O(#hit-topics) python work: one argsort + np.split views instead
    of a per-hit loop (the probes produce ~1 hit/topic at IoT scale, so
    per-hit python would dominate the whole match)."""
    if not ti_parts:
        return out
    ti = np.concatenate(ti_parts)
    rw = np.concatenate(row_parts)
    order = np.argsort(ti, kind="stable")
    ti = ti[order]
    rw = rw[order]
    cuts = np.flatnonzero(ti[1:] != ti[:-1]) + 1
    pieces = np.split(rw, cuts)
    for t, piece in zip(ti[np.concatenate([[0], cuts])], pieces):
        prev = out[t]
        out[t] = piece if not len(prev) else np.concatenate([prev, piece])
    return out


def host_exact_rows_from_sig(tables: SigTables, esig: np.ndarray,
                             lengths: np.ndarray) -> list[np.ndarray]:
    """host_exact_rows when per-topic exact signatures are already computed
    (the C++ tokenizer emits them in its single pass)."""
    out: list[np.ndarray] = [_EMPTY_ROWS] * len(lengths)
    ti_parts: list[np.ndarray] = []
    row_parts: list[np.ndarray] = []
    for d, g in (tables.host_exact or {}).items():
        sel = np.nonzero(lengths == d)[0]
        if not sel.size:
            continue
        _probe_sorted_sigs(g.sigs, g.rows, esig[sel], sel, ti_parts,
                           row_parts)
    return _scatter_hits(out, ti_parts, row_parts)


_EMPTY_ROWS = np.zeros(0, dtype=np.int32)


def host_plus_rows(tables: SigTables, toks: np.ndarray, lengths: np.ndarray,
                   dollar: np.ndarray, into: list | None = None,
                   ge: bool = False) -> list:
    """Vectorized shape probe: for each topic, candidate rows by hashed
    signature equality (per group: one uint32 signature + one
    searchsorted; collisions verified in decode like every other
    candidate). ``toks`` may be any integer dtype — unknown-token
    padding just yields a non-matching signature, exactly as on device.
    Appends into ``into`` (per-topic arrays) when given.

    ``ge=False`` probes the host-resident '+'-shape groups
    (tables.host_plus, applicability depth == d). ``ge=True`` probes
    the '#'-groups instead (tables.host_hash, sorted host views of the
    device rows): applicability becomes depth >= d — the trailing-'#'
    rule incl. the depth-d parent match [MQTT-4.7.1.2] — and the dc
    depth-term is zero by construction."""
    out: list = [_EMPTY_ROWS] * len(lengths) if into is None else into
    width = toks.shape[1]
    ti_parts: list[np.ndarray] = []
    row_parts: list[np.ndarray] = []
    probes = tables.host_hash if ge else tables.host_plus
    for d, p in (probes or {}).items():
        if d > width:
            # deeper shapes only match topics the tokenizer flagged
            # as overflow -> served by the CPU fallback
            continue
        sel = np.nonzero(lengths >= d if ge else lengths == d)[0]
        if not sel.size:
            continue
        t = toks[sel, :max(d, 1)].astype(np.uint32)
        with np.errstate(over="ignore"):
            sig_all = t @ p.coef.T + p.dc[None, :]       # [n, K] wrapping
        dol = dollar[sel]
        for k in range(len(p.sigs)):
            _probe_group_sigs(p, k, sig_all[:, k], sel, dol,
                              ti_parts, row_parts)
    return _scatter_hits(out, ti_parts, row_parts)


def _probe_group_sigs(p, k: int, sig: np.ndarray, sel: np.ndarray,
                      dol: np.ndarray, ti_parts: list,
                      row_parts: list) -> None:
    """Binary-search one wildcard group's sorted signature view,
    applying the [MQTT-4.7.1-1] '$' exclusion for wildcard-first
    shapes."""
    _probe_sorted_sigs(p.sigs[k], p.rows[k], sig, sel, ti_parts,
                       row_parts, dol if p.wildf[k] else None)


def _probe_sorted_sigs(sigs_k: np.ndarray, rows_k: np.ndarray,
                       sig: np.ndarray, sel: np.ndarray, ti_parts: list,
                       row_parts: list,
                       dol: np.ndarray | None = None) -> None:
    """Binary-search a sorted signature array, appending (topic, row)
    hit arrays; signature collisions expand to every colliding row
    (verified later like any candidate). ``dol`` masks '$'-prefixed
    topics out when given."""
    lo = np.searchsorted(sigs_k, sig, side="left")
    ok = (lo < len(sigs_k)) & (sigs_k[
        np.minimum(lo, len(sigs_k) - 1)] == sig)
    if dol is not None:
        ok &= ~dol                        # [MQTT-4.7.1-1] '$' exclusion
    hits = np.nonzero(ok)[0]
    if not hits.size:
        return
    hi = np.searchsorted(sigs_k, sig[hits], side="right")
    lo = lo[hits]
    single = hi - lo == 1                 # collided filters are rare
    ti_parts.append(sel[hits[single]])
    row_parts.append(rows_k[lo[single]])
    for j, l0, h in zip(hits[~single], lo[~single], hi[~single]):
        ti_parts.append(np.full(h - l0, sel[j], dtype=np.int64))
        row_parts.append(rows_k[l0:h])


def host_hash_rows(tables: SigTables, toks: np.ndarray,
                   lengths: np.ndarray, dollar: np.ndarray,
                   into: list | None = None) -> list:
    """Host probe of the DEVICE '#'-groups: host_plus_rows in ge mode.
    Completes the device-free match path — exact + '+' + '#' probes
    together cover every group, so a batch too small to amortize a
    device round trip never has to leave the host."""
    return host_plus_rows(tables, toks, lengths, dollar, into=into,
                          ge=True)


def topic_signatures(consts, toks, lengths):
    """[B, G] uint32 topic signatures. ``consts`` = device SigTables consts
    dict. The per-level loop is static (max_depth is small)."""
    topo_coef = consts["topo_coef"]          # uint32[G, D]
    depth_coef = consts["depth_coef"]        # uint32[G]
    depth = topo_coef.shape[1]
    sig = (lengths.astype(jnp.uint32)[:, None]
           * depth_coef[None, :])            # exact-group depth term
    for lvl in range(min(depth, toks.shape[1])):
        t = toks[:, lvl].astype(jnp.uint32)[:, None]     # [B, 1]
        sig = sig + t * topo_coef[None, :, lvl]          # [B, G]
    return sig


_POISON = jnp.uint32(0x9E3779B9)   # xor'd into invalid-group signatures


def adjusted_signatures(consts, toks, lengths, dollar):
    """[B, G] topic signatures with invalid groups poisoned.

    Group validity ('#'-groups need depth >= prefix, '$'-topics exclude
    wildcard-first groups) is folded into the signature itself: an invalid
    (topic, group) gets its signature xor'd with a constant, so the compare
    stage needs no separate mask operand. A poisoned signature can still
    collide with a row at the 2^-32 baseline rate — host verification
    makes that a perf event, not a correctness event."""
    sig = topic_signatures(consts, toks, lengths)        # [B, G]
    ok = (~consts["is_hash"][None, :]
          | (lengths[:, None] >= consts["min_depth"][None, :]))
    ok = ok & ~(dollar[:, None] & consts["wild_first"][None, :])
    return jnp.where(ok, sig, sig ^ _POISON)


def match_words(consts, planes, sig_adj):
    """[B, W] packed match words from adjusted signatures.

    ``planes`` is uint32[32, W]: plane j holds the signature of bit-j's row
    in each word (row r == 32*w + j). The compare runs as 32 fused
    bit-plane passes over [B, W] — minor axis W tiles the 128-lane VPU
    cleanly, vs. the naive [B, rows/32, 32] layout whose minor axis of 32
    wastes 3/4 of every register. No gathers: the group -> word expansion
    is a concat of broadcasts (group word counts are compile-time static).
    """
    batch = sig_adj.shape[0]
    sizes = consts["group_words_host"]      # python ints: static shapes
    parts = [jnp.broadcast_to(sig_adj[:, g:g + 1], (batch, w))
             for g, w in enumerate(sizes) if w]
    if not parts:
        return jnp.zeros((batch, 1), dtype=jnp.uint32)
    sig_exp = jnp.concatenate(parts, axis=1)             # [B, W]
    acc = jnp.zeros_like(sig_exp)
    for j in range(32):
        acc = acc | ((sig_exp == planes[j][None, :]).astype(jnp.uint32)
                     << jnp.uint32(j))
    return acc


def extract_nonzero_words(words, lengths, max_words: int):
    """Sparse tail of the word path: pick the ≤max_words nonzero uint32
    words of ``words [B, W]`` in ascending word order."""
    nz = words != 0
    n_nz = nz.sum(axis=1, dtype=jnp.int32)
    overflow = (lengths < 0) | (n_nz > max_words)
    # top_k over (nz ? BIG - word_index : -1): picks nonzero words,
    # ascending word index; returns their original indices.
    key = jnp.where(nz, jnp.int32(1 << 30) - jnp.arange(
        words.shape[1], dtype=jnp.int32)[None, :], jnp.int32(-1))
    k = min(max_words, words.shape[1])
    topv, topi = jax.lax.top_k(key, k)
    word_idx = jnp.where(topv > 0, topi, -1)
    word_val = jnp.take_along_axis(words, topi, axis=1)
    word_val = jnp.where(topv > 0, word_val, jnp.uint32(0))
    if k < max_words:        # tiny tables: pad out to the fixed contract
        pad = max_words - k
        word_idx = jnp.pad(word_idx, ((0, 0), (0, pad)),
                           constant_values=-1)
        word_val = jnp.pad(word_val, ((0, 0), (0, pad)))
    return word_idx, word_val, overflow


def sig_match_body(consts, planes, toks, lengths, dollar, max_words: int):
    """Traceable signature match over one topic batch (word output form).

    Returns (word_idx, word_val, overflow): extract_nonzero_words."""
    sig_adj = adjusted_signatures(consts, toks, lengths, dollar)
    words = match_words(consts, planes, sig_adj)
    return extract_nonzero_words(words, lengths, max_words)


def sig_match_compact_body(consts, planes, toks8, lens_enc,
                           max_word_slots: int, max_rows: int, cap: int):
    """Transfer-minimal match: narrow tokens in, row-id stream out.

    Inputs (sized for the host->device link, see tokenize_compact):
      toks8: uint8/uint16/int32[B, D] level tokens over the static window
        D = tables.max_depth (pad = max dtype value);
      lens_enc: int8[B] — sign bit carries the '$'-flag, |value| is the
        TRUE topic depth (up to 63; 127 = deeper, overflow).

    Outputs (sized for the device->host link):
      counts: uint8[B] — matched candidate rows per topic (255 = overflow:
        topic too deep, >max_word_slots nonzero words, or >max_rows rows);
      stream: uint32[cap] — row ids, all topics' matches concatenated in
        topic order (slice b = stream[cumsum[b-1]:cumsum[b]]);
      total: int32 — valid entries in stream (> cap means the batch
        overflowed the stream and the host must fall back for it).

    ~1 + 4*matches bytes per topic instead of 8*max_words — the difference
    between 60K and >1M matches/sec through a narrow host<->device link.
    """
    batch = toks8.shape[0]
    dollar = lens_enc < 0
    lengths = jnp.abs(lens_enc.astype(jnp.int32))
    too_deep = lengths >= 127
    toks = toks8.astype(jnp.int32)

    sig_adj = adjusted_signatures(consts, toks, lengths, dollar)
    words = match_words(consts, planes, sig_adj)         # [B, W]
    n_words = words.shape[1]

    # per-topic top word slots (ascending word index)
    nz = words != 0
    n_nz = nz.sum(axis=1, dtype=jnp.int32)
    key = jnp.where(nz, jnp.int32(1 << 30) - jnp.arange(
        n_words, dtype=jnp.int32)[None, :], jnp.int32(-1))
    max_word_slots = min(max_word_slots, n_words)
    topv, topi = jax.lax.top_k(key, max_word_slots)      # [B, S]
    wvals = jnp.where(topv > 0,
                      jnp.take_along_axis(words, topi, axis=1),
                      jnp.uint32(0))

    # expand words to candidate row ids [B, S*32]
    bit = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    valid = ((wvals[:, :, None] >> bit) & 1) == 1        # [B, S, 32]
    rowid = (topi[:, :, None].astype(jnp.uint32) << 5) | bit
    valid = valid.reshape(batch, -1)
    rowid = rowid.reshape(batch, -1)

    counts = valid.sum(axis=1, dtype=jnp.int32)          # candidate rows
    overflow = too_deep | (n_nz > max_word_slots) | (counts > max_rows)

    # per-topic compaction to max_rows slots (ascending slot order)
    key2 = jnp.where(valid, jnp.int32(1 << 30) - jnp.arange(
        rowid.shape[1], dtype=jnp.int32)[None, :], jnp.int32(-1))
    v2, i2 = jax.lax.top_k(key2, max_rows)               # [B, R]
    rows_k = jnp.take_along_axis(rowid, i2, axis=1)
    valid_k = (v2 > 0) & ~overflow[:, None]

    # batch compaction: stable sort moves valid entries to the front in
    # (topic, slot) order; the stream is the first `cap` payloads
    flat_valid = valid_k.reshape(-1)
    flat_rows = rows_k.reshape(-1)
    order_key = jnp.where(flat_valid,
                          jnp.arange(flat_rows.shape[0], dtype=jnp.int32),
                          jnp.int32(0x7FFFFFFF))
    _, stream = jax.lax.sort([order_key, flat_rows], num_keys=1)
    stream = stream[:cap]

    counts_u8 = jnp.where(overflow, 255,
                          jnp.minimum(counts, 254)).astype(jnp.uint8)
    total = jnp.where(overflow, 0, counts).sum(dtype=jnp.int32)
    return counts_u8, stream, total


def _ctz32(v):
    """Count trailing zeros of nonzero uint32 (elementwise, branch-free)."""
    lsb = v & (~v + jnp.uint32(1))
    m = lsb - jnp.uint32(1)
    m = m - ((m >> 1) & jnp.uint32(0x55555555))
    m = (m & jnp.uint32(0x33333333)) + ((m >> 2) & jnp.uint32(0x33333333))
    return (((m + (m >> 4)) & jnp.uint32(0x0F0F0F0F))
            * jnp.uint32(0x01010101)) >> 24


def _popc32(v):
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    return (((v + (v >> 4)) & jnp.uint32(0x0F0F0F0F))
            * jnp.uint32(0x01010101)) >> 24


def fixed_slots_from_words(words, too_deep, sel_blocks: int, max_rows: int,
                           fmt16: bool):
    """Shared tail of the fixed-slot matchers (single-device and sharded):
    [B, W] match words -> packed fixed output (see sig_match_fixed_body).
    """
    batch = words.shape[0]
    n_words = words.shape[1]
    ws = (n_words + 31) // 32
    pad = ws * 32 - n_words

    # summary bitmap: bit t of summary word s == (word 32s+t nonzero)
    nz = words != 0
    if pad:
        nz = jnp.pad(nz, ((0, 0), (0, pad)))
    bits = nz.reshape(batch, ws, 32)
    summary = (bits.astype(jnp.uint32)
               << jnp.arange(32, dtype=jnp.uint32)[None, None, :]).sum(
                   axis=2, dtype=jnp.uint32)             # [B, WS]

    snz = summary != 0
    n_blocks = snz.sum(axis=1, dtype=jnp.int32)
    key = jnp.where(snz, jnp.int32(1 << 30) - jnp.arange(
        ws, dtype=jnp.int32)[None, :], jnp.int32(-1))
    sel_blocks = min(sel_blocks, ws)
    topv, sel = jax.lax.top_k(key, sel_blocks)           # [B, SB]
    sel = jnp.where(topv > 0, sel, 0)

    if pad:
        words = jnp.pad(words, ((0, 0), (0, pad)))
    blocks = words.reshape(batch, ws, 32)
    g = jnp.take_along_axis(blocks, sel[:, :, None], axis=1)  # [B, SB, 32]
    g = jnp.where((topv > 0)[:, :, None], g, jnp.uint32(0))
    wordidx = (sel[:, :, None].astype(jnp.uint32) << 5) | \
        jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    g = g.reshape(batch, -1)                             # [B, SB*32]
    wordidx = wordidx.reshape(batch, -1)

    counts = _popc32(g).sum(axis=1, dtype=jnp.int32)
    overflow = too_deep | (n_blocks > sel_blocks) | (counts > max_rows)

    rows = []
    inf = jnp.uint32(0xFFFFFFFF)
    for _ in range(max_rows):
        enc = jnp.where(g != 0, (wordidx << 5) | _ctz32(g), inf)
        m = enc.min(axis=1)                              # [B]
        rows.append(m)
        hit = enc == m[:, None]
        g = jnp.where(hit, g & (g - jnp.uint32(1)), g)   # clear lowest bit

    cnt = jnp.where(overflow, jnp.uint32(0xF),
                    jnp.minimum(counts, max_rows).astype(jnp.uint32))
    if fmt16:
        # pack: word0 = count<<28 | row0; then rows 2-at-a-time per word
        row16 = [jnp.where(r == inf, jnp.uint32(0xFFFF), r & 0xFFFF)
                 for r in rows]
        out = [cnt << 28 | row16[0]]
        for i in range(1, max_rows, 2):
            hi = row16[i + 1] if i + 1 < max_rows else jnp.uint32(0xFFFF)
            out.append(hi << 16 | row16[i])
        return jnp.stack(out, axis=1)                    # uint32[B, 1+k/2]
    return jnp.concatenate(
        [cnt[:, None]] + [r[:, None] for r in rows], axis=1)


def sig_match_words_gather(consts, planes, grp_of_word, toks, lengths,
                           dollar):
    """[B, W] match words with a gather-based group expansion.

    The concat-of-broadcasts in match_words needs compile-time-static group
    word counts — impossible under shard_map, where ONE program serves
    every shard's tables. Here the word -> group map is a device array
    (``grp_of_word`` int32[W]) and the expansion is a small gather from
    [B, G]. Single-device engines keep the static concat (faster);
    the sharded engine uses this form."""
    sig_adj = adjusted_signatures(consts, toks, lengths, dollar)
    sig_exp = jnp.take(sig_adj, grp_of_word, axis=1)     # [B, W]
    acc = jnp.zeros_like(sig_exp)
    for j in range(32):
        acc = acc | ((sig_exp == planes[j][None, :]).astype(jnp.uint32)
                     << jnp.uint32(j))
    return acc


def sig_match_fixed_body(consts, planes, toks8, lens_enc,
                         sel_blocks: int, max_rows: int):
    """Fixed-slot match: the fewest-bytes, fewest-kernels device program.

    Where sig_match_compact_body builds a variable-length stream (top_k +
    global sort — the expensive XLA ops), this returns AT MOST ``max_rows``
    row ids per topic in fixed slots, packed with the candidate count into
    ONE uint32[B, 1 + ceil(max_rows/2)] output when rows fit uint16
    (n_rows <= 65536), else int32[B, 1 + max_rows]. One device buffer each
    way; topics with more candidates flag overflow (count 0xF) and fall
    back to the CPU trie — sized so that's a percent-level event.

    Pipeline (2 full passes over the [B, W] word matrix, everything else
    is narrow):
      words -> nonzero-summary bitmap [B, W/32] -> top_k of ``sel_blocks``
      summary blocks -> gather their 32-word slices -> ``max_rows``
      min-extract+clear iterations at bit level -> packed slots.
    """
    dollar = lens_enc < 0
    lengths = jnp.abs(lens_enc.astype(jnp.int32))
    too_deep = lengths >= 127
    toks = toks8.astype(jnp.int32)

    sig_adj = adjusted_signatures(consts, toks, lengths, dollar)
    words = match_words(consts, planes, sig_adj)         # [B, W]
    return fixed_slots_from_words(words, too_deep, sel_blocks, max_rows,
                                  fmt16=words.shape[1] * 32 <= 65536)


def _compact_dtype(tables):
    nv = len(tables.vocab)
    if nv < 250:
        return np.uint8, 255
    if nv < 65000:
        return np.uint16, 65535
    return np.int32, -1


def tokenize_compact(tables, topics: list[str], window: int | None = None):
    """Host-side compact topic prep: (toks, lens_enc, toks32, lengths).

    toks/lens_enc follow sig_match_compact_body's contract — token dtype
    adapts to the vocab (uint8 < 250 ids, uint16 < 65000, else int32); the
    wide form (toks32) also feeds the host-exact probe. This is the pure
    numpy path; prepare_batch uses the one-pass C++ tokenizer when built.
    """
    if window is None:
        window = max(tables.probe_depth, 1)
    toks32, lengths, dollar = tokenize_topics(tables.vocab, topics,
                                              DEPTH_CAP)
    dtype, pad = _compact_dtype(tables)
    w = toks32[:, :window]
    toks = np.where(w < 0, pad, w).astype(dtype)
    true_len = np.where(lengths < 0, 127, lengths).astype(np.int8)
    lens_enc = np.where(dollar, -true_len, true_len).astype(np.int8)
    return toks, lens_enc, toks32, lengths


def prepare_batch_sig(tables, topics: list[str], window: int | None = None,
                      host_exact: dict | None = None):
    """Host half of the compact/fixed paths, signature form: (toks,
    lens_enc, esig, lengths). One C++ pass (tokens + exact-group
    signatures) when the native runtime is built; numpy otherwise.

    ``window``/``host_exact`` override the tables' own (the sharded engine
    passes the mesh-wide maxima/union — exact-group coefficients are
    deterministic functions of the group shape, so one signature per depth
    serves every shard)."""
    if window is None:
        window = max(tables.probe_depth, 1)
    if host_exact is None:
        host_exact = tables.host_exact or {}
    ns = tables.__dict__.get("_native_sig", False)
    if ns is False:
        ns = None
        try:
            from ..native import ExactSigTable, NativeVocab, available
            if available():
                # share the C++ vocab mirror with the word path
                # (tokenize_cached caches it under _native_vocab) instead
                # of marshalling the whole vocab into C++ twice
                nv = tables.__dict__.get("_native_vocab") or \
                    NativeVocab(tables.vocab)
                tables.__dict__.setdefault("_native_vocab", nv)
                ns = (nv, ExactSigTable(host_exact))
        except Exception:
            ns = None
        tables.__dict__["_native_sig"] = ns
    if ns is None:
        toks, lens_enc, toks32, lengths = tokenize_compact(tables, topics,
                                                           window)
        return toks, lens_enc, exact_sigs(host_exact, toks32, lengths), \
            lengths
    from ..native import tokenize_sig
    dtype, _pad = _compact_dtype(tables)
    toks, lens_enc, esig = tokenize_sig(ns[0], topics, window, dtype, ns[1])
    lengths = np.abs(lens_enc.astype(np.int32))
    lengths[lengths >= 127] = -1
    return toks, lens_enc, esig, lengths


class HostRows:
    """CSR view of the host probe's per-topic candidate rows: O(1) python
    work per batch instead of one list entry per topic. Supports the same
    consumer surface as a list of per-topic arrays (index, iterate, and
    the `[:batch]` trim the sharded engine uses)."""

    __slots__ = ("offsets", "rows")

    def __init__(self, offsets: np.ndarray, rows: np.ndarray) -> None:
        self.offsets = offsets        # int64[n + 1]
        self.rows = rows              # int32[total hits]

    @classmethod
    def from_hits(cls, n: int, ti: np.ndarray, rows: np.ndarray
                  ) -> "HostRows":
        counts = np.bincount(ti, minlength=n)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        return cls(offsets, rows)

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def __getitem__(self, i):
        if isinstance(i, slice):
            assert i.start is None and i.step is None
            k = min(i.stop if i.stop is not None else len(self), len(self))
            return HostRows(self.offsets[:k + 1],
                            self.rows[:self.offsets[k]])
        return self.rows[self.offsets[i]:self.offsets[i + 1]]

    def __iter__(self):
        for i in range(len(self)):
            yield self.rows[self.offsets[i]:self.offsets[i + 1]]


def _native_fused(tables):
    """(NativeVocab, NativeProbe) pair for the fused single-pass host
    half, or None. Cached per compiled-table snapshot."""
    fused = tables.__dict__.get("_native_fused", False)
    if fused is not False:
        return fused
    fused = None
    try:
        from ..native import NativeProbe, NativeVocab, available
        if available():
            nv = tables.__dict__.get("_native_vocab") or \
                NativeVocab(tables.vocab)
            tables.__dict__.setdefault("_native_vocab", nv)
            fused = (nv, NativeProbe(tables.host_exact or {},
                                     tables.host_plus or {}))
    except Exception:
        fused = None
    tables.__dict__["_native_fused"] = fused
    return fused


def _native_hash_probe(tables):
    """NativeProbe over the '#'-groups in depth->= mode (the C twin of
    host_hash_rows), or None. Cached per compiled-table snapshot. Only
    the device-free paths run it (bound into _native_host, and the
    sharded engine's) — the device still owns '#'-matching for batched
    dispatches."""
    probe = tables.__dict__.get("_native_hash_probe", False)
    if probe is not False:
        return probe
    probe = None
    try:
        from ..native import NativeProbe, available
        if available() and tables.host_hash is not None:
            probe = NativeProbe({}, tables.host_hash, ge_depth=True)
    except Exception:
        probe = None
    tables.__dict__["_native_hash_probe"] = probe
    return probe


def prepare_batch(tables, topics: list[str]):
    """Full host half for the compact/fixed paths: (toks, lens_enc,
    hostrows). hostrows unions the full-exact esig probe and the
    '+'-shape probe — everything the device no longer carries. One fused
    C++ pass (tokenize + probe with the level tokens in registers) when
    the native runtime is built; numpy otherwise."""
    fused = _native_fused(tables)
    if fused is not None:
        from ..native import tokenize_probe
        dtype, _pad = _compact_dtype(tables)
        window = max(tables.probe_depth, 1)
        toks, lens_enc, ti, rw = tokenize_probe(fused[0], fused[1], topics,
                                                window, dtype)
        return toks, lens_enc, HostRows.from_hits(len(topics), ti, rw)
    toks, lens_enc, esig, lengths = prepare_batch_sig(tables, topics)
    hostrows = host_exact_rows_from_sig(tables, esig, lengths)
    host_plus_rows(tables, toks, lengths, lens_enc < 0, into=hostrows)
    return toks, lens_enc, hostrows


_STREAM_CHUNK = 1 << 19    # rows per stream-slice fetch (2 MB of uint32).
                           # Slice bounds are static multiples of this, so
                           # every slice shape compiles exactly once and
                           # only the used front of the capacity-padded
                           # stream ever crosses the link.


_VER_PLUS = -1    # '+' level in the verify tables: matches any token
_VER_ANY = -2     # position past the filter (or past the probe window)


def _verify_arrays(tables):
    """Row-side tables for the vectorized candidate verifier, built once
    per compiled snapshot (cached): per row, the literal token at each
    probe-window position (or PLUS/ANY), the required depth, exactness,
    and the '$'-exclusion flag. Together these reproduce
    ``topics.filter_matches_topic`` as pure array comparisons."""
    vt = tables.__dict__.get("_verify_arrays")
    if vt is not None:
        return vt
    n_rows = len(tables.row_levels)
    window = max(tables.probe_depth, 1)
    tok = np.full((n_rows, window), _VER_ANY, dtype=np.int32)
    min_depth = np.zeros(n_rows, dtype=np.int32)
    exact = np.zeros(n_rows, dtype=bool)
    wild_first = np.zeros(n_rows, dtype=bool)
    valid = np.zeros(n_rows, dtype=bool)
    vocab = tables.vocab
    for r, levels in enumerate(tables.row_levels):
        if not levels:
            continue
        valid[r] = True
        is_hash = levels[-1] == "#"
        depth = len(levels) - 1 if is_hash else len(levels)
        min_depth[r] = depth
        exact[r] = not is_hash
        wild_first[r] = levels[0] in ("+", "#")
        for i in range(min(depth, window)):
            lv = levels[i]
            # a literal never in the vocab cannot exist post-compile; -3
            # (matches nothing) keeps even that case safe
            tok[r, i] = _VER_PLUS if lv == "+" else vocab.get(lv, -3)
    vt = (tok, min_depth, exact, wild_first, valid)
    tables.__dict__["_verify_arrays"] = vt
    return vt


def _decode_cache(tables):
    """Per-row fast-path decode arrays (cached per snapshot): for rows
    whose single entry is a plain (client, sub) with no v5 subscription
    identifier, the union is two dict ops — no Entry walk, no merge
    allocation. Rows with shared groups, multiple entries, or
    identifiers keep the exact slow path."""
    dc = tables.__dict__.get("_decode_cache")
    if dc is not None:
        return dc
    entries = tables.entries
    cids: list[str | None] = []
    subs: list = []
    for ents in tables.row_entries:
        if len(ents) == 1:
            e = entries[ents[0]]
            if not e.group and e.subscription is not None \
                    and not e.subscription.identifier \
                    and not e.subscription.identifiers:
                cids.append(e.client_id)
                subs.append(e.subscription)
                continue
        cids.append(None)
        subs.append(None)
    dc = (cids, subs)
    tables.__dict__["_decode_cache"] = dc
    return dc


def prewarm_tables(tables, chunk: int = 2048) -> int:
    """Chunked chained-decode anchor population for ONE compiled table
    (the shared engine-independent half of prewarm_decode_bases):
    yields the GIL between chunks so an event loop sharing the
    interpreter only stalls ~ms at a time. Returns chunk calls made."""
    import time as _time

    nd = _native_decode(tables)
    if nd is None or not hasattr(nd[0], "prewarm_bases"):
        return 0
    mod, cap = nd
    n_rows = len(tables.row_entries)
    r = 0
    calls = 0
    while r < n_rows:
        r2 = mod.prewarm_bases(cap, r, chunk)
        calls += 1
        if r2 <= r:
            break                  # defensive: no forward progress
        r = r2
        _time.sleep(0)
    return calls


def _native_decode(tables):
    """(maxmq_decode module, table capsule) for the C verify+union fast
    path, built once per compiled snapshot — or None when the extension
    is unavailable. Flattens every row's entry walk (the exact loop in
    decode_fixed's python fallback) into an action stream the C pass
    replays: PLAIN inserts, identifier MERGEs, SHARED-group inserts.
    The capsule's Py_buffer views keep the arrays alive."""
    nd = tables.__dict__.get("_native_decode", False)
    if nd is not False:
        return nd
    nd = None
    try:
        from ..native import decode_module
        mod = decode_module()
        # engage only when trie.py's import-time rebind took: decode
        # returns instances of mod.SubscriberSet, and mixing C results
        # with the python fallback class would split the result type
        if mod is not None and mod.SubscriberSet is SubscriberSet:
            tok, min_depth, exact, wild_first, valid = \
                _verify_arrays(tables)
            flags = (exact.astype(np.uint8)
                     | (wild_first.astype(np.uint8) << 1)
                     | (valid.astype(np.uint8) << 2))
            entries = tables.entries
            offsets = np.zeros(len(tables.row_entries) + 1,
                               dtype=np.int64)
            kinds: list[int] = []
            keys: list = []
            cids: list = []
            subs: list = []
            for r, ents in enumerate(tables.row_entries):
                for b in ents:
                    e = entries[b]
                    if e.group:
                        for cid, sub in e.candidates.items():
                            kinds.append(2)
                            keys.append((e.group, sub.filter))
                            cids.append(cid)
                            subs.append(sub)
                    else:
                        sub = e.subscription
                        kinds.append(1 if (sub.identifier
                                           or sub.identifiers) else 0)
                        keys.append(sub.filter)
                        cids.append(e.client_id)
                        subs.append(sub)
                offsets[r + 1] = len(kinds)
            cap = mod.table_new(
                np.ascontiguousarray(tok),
                np.ascontiguousarray(min_depth), flags, offsets,
                np.array(kinds, dtype=np.uint8), keys, cids, subs)
            if hasattr(mod, "table_release"):
                # cached DeliveryIntents hold the capsule alive and the
                # capsule's caches hold them — an uncollectible cycle
                # (capsules aren't GC-tracked). Break it when the
                # snapshot is dropped; handed-out results stay valid.
                import weakref
                weakref.finalize(tables, mod.table_release, cap)
            nd = (mod, cap)
    except Exception:
        nd = None
    tables.__dict__["_native_decode"] = nd
    return nd


# host_batch's result forms (native/maxmq_decode.cpp)
_FORM_SET, _FORM_INTENTS, _FORM_PAIRS = 0, 1, 2
# its three parts, as a batch record names them (ADR 015)
_HOST_PARTS = ("match_prep", "match_probe", "match_decode")


def _part_marker(rec):
    """What host_batch calls as each of its parts begins while a batch
    record is active: the record's phases, with their profiler
    annotations, bounded where the parts are."""
    def begin(k: int) -> None:
        if k:
            rec.end()
        rec.begin(_HOST_PARTS[k])
    return begin


def _native_host(tables):
    """(maxmq_decode.host_batch, table capsule, probe capsule) for the
    one-call device-free match, or None. Binds the snapshot's native
    vocab, exact/'+' probe and '#' probe to the decode table once per
    compiled snapshot (cached); None when either native library is
    missing, and the numpy path answers."""
    nh = tables.__dict__.get("_native_host", False)
    if nh is not False:
        return nh
    nh = None
    try:
        nd = _native_decode(tables)
        fused = _native_fused(tables)
        hp = _native_hash_probe(tables)
        if (nd is not None and fused is not None
                and (hp is not None or tables.host_hash is None)):
            from ..native import bind_host_probe
            mod, cap = nd
            dtype, pad = _compact_dtype(tables)
            hcap = bind_host_probe(mod, *fused, hp,
                                   max(tables.probe_depth, 1),
                                   np.dtype(dtype).itemsize, int(pad))
            nh = (mod.host_batch, cap, hcap)
    except Exception:
        nh = None
    tables.__dict__["_native_host"] = nh
    return nh


def _pairs_with_host(batch: int, ti_dev, rw_dev, hostrows, fall, tables):
    """Concatenate device pairs with the host-probe hits and drop
    fallback topics / out-of-table row ids (group-padded layouts emit
    padding row ids past the real table)."""
    if isinstance(hostrows, HostRows):
        offs = hostrows.offsets[:batch + 1]
        ti_h = np.repeat(np.arange(batch), np.diff(offs))
        rw_h = hostrows.rows[:offs[-1]].astype(np.int64)
    else:
        ti_h = np.repeat(np.arange(batch),
                         [len(h) for h in hostrows[:batch]])
        rw_h = (np.concatenate([np.asarray(h) for h in
                                hostrows[:batch]]).astype(np.int64)
                if len(ti_h) else np.empty(0, dtype=np.int64))
    ti = np.concatenate([ti_dev, ti_h])
    rw = np.concatenate([rw_dev, rw_h])
    keep = ~fall[ti] & (rw < len(tables.row_levels))
    return ti[keep], rw[keep]


def _candidate_pairs(batch: int, cnt, rows, hostrows, fall, tables):
    """Flatten device slots + host-probe hits into (topic_idx, row_id)
    pair arrays, dropping fallback topics and out-of-table row ids."""
    kr = rows.shape[1]
    real = np.where(fall, 0, cnt).astype(np.int64)
    dmask = np.arange(kr, dtype=np.int64)[None, :] < real[:, None]
    ti_dev = np.repeat(np.arange(batch), real)
    rw_dev = rows[dmask].astype(np.int64)
    return _pairs_with_host(batch, ti_dev, rw_dev, hostrows, fall, tables)


def verify_pairs(tables, toks32, lengths, dollar, ti, rw) -> np.ndarray:
    """Vectorized ``filter_matches_topic`` over candidate (topic, row)
    pairs: ok[n] == the exact CPU check for topic ``ti[n]`` vs row
    ``rw[n]``. All literal filter positions sit inside the probe window
    (the compile invariant behind ``probe_depth``); positions beyond it
    are '+'-only and are covered by the depth comparison."""
    tok, min_depth, exact, wild_first, valid = _verify_arrays(tables)
    rt = tok[rw]                                  # [N, W]
    tt = toks32[ti][:, :rt.shape[1]]              # [N, W]
    ok = ((rt == _VER_ANY) | (rt == _VER_PLUS) | (rt == tt)).all(axis=1)
    md = min_depth[rw]
    ln = lengths[ti]
    ok &= np.where(exact[rw], ln == md, ln >= md)
    ok &= ~(dollar[ti] & wild_first[rw])
    ok &= valid[rw]
    return ok


def _union_pairs(out, ti, rw, tables) -> None:
    """Union verified candidate pairs into the per-topic SubscriberSets.
    Hot loop: fast-path rows (single plain subscription) are two dict
    ops; merge_subscription aliases the stored Subscription."""
    entries = tables.entries
    row_entries = tables.row_entries
    fast_cid, fast_sub = _decode_cache(tables)
    dicts = [s.subscriptions for s in out]
    merge = merge_subscription
    for t, r in zip(ti.tolist(), rw.tolist()):
        cid = fast_cid[r]
        if cid is not None:
            d = dicts[t]
            sub = fast_sub[r]
            cur = d.get(cid)
            d[cid] = sub if cur is None else merge(cur, sub, sub.filter)
            continue
        result = out[t]
        for b in row_entries[r]:
            entry = entries[b]
            if entry.group:
                for cid, sub in entry.candidates.items():
                    result.add_shared(entry.group, sub.filter, cid, sub)
            else:
                sub = entry.subscription
                result.add(entry.client_id, sub, sub.filter)


def _union_pairs_removed(out, ti, rw, tables, removed) -> None:
    """Union loop for the overlay case over verified (topic, row) id
    lists: (client, filter) pairs the host overlay has removed are
    filtered out row by row."""
    entries = tables.entries
    row_entries = tables.row_entries
    for t, r in zip(ti, rw):
        result = out[t]
        for b in row_entries[r]:
            entry = entries[b]
            if entry.group:
                for cid, sub in entry.candidates.items():
                    if (cid, sub.filter) in removed:
                        continue
                    result.add_shared(entry.group, sub.filter, cid, sub)
            else:
                sub = entry.subscription
                if (entry.client_id, sub.filter) in removed:
                    continue
                result.add(entry.client_id, sub, sub.filter)


class Overlay:
    """Host-side view of subscription mutations newer than the compiled
    tables, replayed from the TopicIndex journal.

    Matching never waits on a table recompile: adds live in a small delta
    TopicIndex (matched per topic with the CPU trie and unioned in),
    removes/replaces live in a (client_id, filter) set consulted during
    decode. A recompile runs in the background; once it swaps in, the
    overlay for the old tables is dropped."""

    def __init__(self, base_version: int) -> None:
        self.base = base_version        # construction base (tables version)
        self.version = base_version     # last applied sub_version
        self.delta = TopicIndex()
        self.removed: set[tuple[str, str]] = set()

    def apply(self, entries) -> None:
        for ver, op, client_id, filt, sub, _group, _path in entries:
            if ver <= self.version:
                continue
            self.version = ver
            # '+' doubles as replace: the stale tables may hold an older
            # subscription (different QoS/options) for the same pair
            self.removed.add((client_id, filt))
            if op == "+":
                self.delta.subscribe(client_id, sub)
            else:
                self.delta.unsubscribe(client_id, filt)

    @property
    def empty(self) -> bool:
        return not self.removed


class OverlayedEngine:
    """The device engine as its callers see it, and the staleness
    machinery (background recompile + journal overlay) its two
    implementations share: ``SigEngine`` (one chip) and
    ``parallel.sharded.ShardedSigEngine`` (a mesh).

    What ``MicroBatcher``, ``bootstrap.build_matcher`` and
    ``Broker._compile_matcher_tables`` may call on either engine:

    * answers, each exact against ``index`` (the live ``TopicIndex``):
      ``subscribers(topic)``, ``subscribers_async(topic)``,
      ``subscribers_batch(topics)`` (the device) and
      ``subscribers_host_batch(topics)`` (the device-free probe the
      batcher's bypass takes). A result is a ``SubscriberSet`` or, with
      ``emit_intents`` set, a fan-out-ready intents object with
      ``to_set()`` (ADR 007);
    * tables: ``refresh(force)`` compiles on the calling thread,
      ``refresh_soon()`` in the background (unless ``auto_refresh`` is
      off), ``compiling`` says one is running, ``close()`` waits for it;
    * warm-up: ``warm_buckets(max_batch)`` names the served bucket
      ladder, ``rewarm()`` compiles it against the live program,
      ``prewarm_decode_bases()`` builds the chained-decode anchors;
    * counters: ``matches``, ``fallbacks``, ``host_matches``,
      ``bg_refresh_errors``, ``warm_seconds``; ``tracer`` (ADR 015).

    ``SigEngine`` alone has more, and its callers probe for it: the
    fixed-slot surface (``subscribers_fixed_batch``, and the
    ``dispatch_fixed`` / ``collect_fixed`` split with the ADR-008 router
    ``_routes_to_trie`` that pipelining needs), ``trie_routed``,
    ``host_fused_batches`` and ``kernel_plan``. A mesh engine's ``subscribers_batch`` is its
    fixed-slot path.

    Subclasses provide ``index``, ``_state``, a ``_refresh_lock`` and
    the methods below that raise ``NotImplementedError``."""

    # background recompile on a stale match; SigEngine takes it as a
    # constructor argument
    auto_refresh = True

    def _init_overlay(self) -> None:
        self._overlay: Overlay | None = None
        self._overlay_lock = threading.Lock()
        self._bg_thread: threading.Thread | None = None
        self._warm_thread: threading.Thread | None = None
        self._warm_max = 0          # largest served batch (warm_buckets)
        self.warm_seconds = 0.0     # host seconds of the newest warm
        # background compiles that failed: table rotations and bucket
        # warms, each logged when counted
        self.bg_refresh_errors = 0
        # the broker's PipelineTracer (bootstrap.build_matcher): while
        # it samples, a rotation's host work is annotated for a
        # profiler capture (ADR 015)
        self.tracer = None

    def _span(self, name: str):
        """A profiler annotation for host work on this thread while
        the broker's tracer samples, else nothing."""
        tracer = self.tracer
        return (host_span(name) if tracer is not None and tracer.sample_n
                else NO_SPAN)

    def refresh_soon(self) -> None:
        """Kick a background recompile if the tables are stale and none is
        already running. Never blocks the caller."""
        if not self._stale():
            return
        with self._overlay_lock:
            if self._bg_thread is not None and self._bg_thread.is_alive():
                return
            t = threading.Thread(target=self._bg_refresh, daemon=True,
                                 name="sig-refresh")
            self._bg_thread = t
            t.start()

    def _stale(self) -> bool:
        state = self._state
        return state is None or self._state_version(state) != \
            self.index.sub_version

    @property
    def compiling(self) -> bool:
        """A background compile (table rotation or bucket warm) is in
        flight: host-heavy work sharing the interpreter with the match
        path, so a round trip timed now says little about the device."""
        return any(t is not None and t.is_alive()
                   for t in (self._bg_thread, self._warm_thread))

    def close(self, timeout: float = 30.0) -> None:
        """Wait for in-flight background compiles (refresh AND bucket
        warm). Killing the interpreter while a compile runs inside the
        runtime library aborts the process; joining here keeps shutdown
        clean."""
        for t in (self._bg_thread, self._warm_thread):
            if t is not None and t.is_alive():
                t.join(timeout)

    def rewarm(self) -> None:
        """Compile the served bucket ladder against the live program,
        on the calling thread, raising what the compiler raises. Every
        table compile swaps in a fresh jitted program, so this follows
        each one: the boot compile (Broker.serve) and every background
        rotation. A no-op for an engine with no ladder named yet."""
        if self._warm_max:
            self.warm_buckets(self._warm_max, background=False)

    def warm_buckets(self, max_batch: int = 4096,
                     background: bool = True) -> None:
        """Precompile the device program at the broker-relevant bucket
        shapes (the ``batch_bucket`` ladder up to ``max_batch``), so the
        first real publishes never pay a multi-second XLA compile. The
        warm topic is a '$'-prefixed dummy that matches nothing.
        Synchronous calls raise a compile error; a background one
        counts and logs it (``bg_refresh_errors``)."""
        self._warm_max = max_batch      # re-warmed after each table compile
        if not background:
            self._warm(max_batch)
            return

        def _warm_bg():
            try:
                self._warm(max_batch)
            except Exception:
                self._note_bg_error("bucket warm")
        t = threading.Thread(target=_warm_bg, daemon=True, name="sig-warm")
        self._warm_thread = t
        t.start()

    def _warm(self, max_batch: int) -> None:
        if not self._has_program():
            return      # the trie serves this corpus: no program to warm
        sizes, b = [], 16
        while b < max_batch:
            sizes.append(b)
            b = _batch_bucket(b + 1)    # the exact dispatch ladder
        sizes.append(_batch_bucket(max_batch))
        t0 = time.perf_counter()
        with self._span("maxmq.bucket_warm"):
            for size in sizes:
                self._warm_one(size)
        self.warm_seconds = time.perf_counter() - t0

    def _has_program(self) -> bool:
        """Whether a compiled device program serves the live corpus."""
        raise NotImplementedError

    def _warm_one(self, size: int) -> None:
        """Run one ``size``-topic batch of the warm topic through the
        device program and wait for it."""
        raise NotImplementedError

    def _bg_refresh(self) -> None:
        try:
            self.refresh()
            # still on this background thread, so the next real batches
            # don't pay the per-shape compiles
            self.rewarm()
            # repopulate the chained-decode anchors for the fresh
            # table off the hot path (chunked; yields the GIL)
            self.prewarm_decode_bases()
        except Exception:
            self._note_bg_error("table rotation")
        finally:
            with self._overlay_lock:
                ov = self._overlay
                if ov is not None and ov.version <= self._state_version(
                        self._state):
                    self._overlay = None

    def _note_bg_error(self, what: str) -> None:
        """Count and log a compile that failed off the caller's path
        (call from the except block): the last-good program keeps
        serving, which is why nothing else would say so."""
        self.bg_refresh_errors += 1
        logging.getLogger("maxmq.matcher").exception(
            "background %s failed; serving the last-good program", what)

    def overlay_for(self, tables_version: int):
        """The overlay bringing ``tables_version`` up to the live index,
        or None when up to date, or the string "resync" when the journal
        no longer reaches back (serve the batch via the CPU trie)."""
        if self.index.sub_version == tables_version:
            return None
        if self.auto_refresh:
            self.refresh_soon()
        with self._overlay_lock:
            ov = self._overlay
            # Key reuse on the construction base, not the applied-through
            # version: an overlay rebuilt against NEWER tables (base v10)
            # must not serve a batch still holding OLD tables (v8) — the
            # entries in (8,10] would be in neither. Reusing an
            # older-based overlay is safe (replay is idempotent).
            if ov is None or ov.base > tables_version:
                ov = Overlay(tables_version)
            entries = self.index.journal_since(ov.version)
            if entries is None:
                return "resync"
            ov.apply(entries)
            self._overlay = ov
            return None if ov.empty else ov

    @staticmethod
    def _state_version(state) -> int:
        raise NotImplementedError

    def refresh(self, force: bool = False) -> bool:
        """Recompile and upload if the index changed, on the calling
        thread, raising what the compiler raises."""
        raise NotImplementedError

    def prewarm_decode_bases(self, chunk: int = 2048) -> int:
        """Build the chained-decode anchors of the live tables; returns
        the chunk calls made (0 when the intents decode is off)."""
        raise NotImplementedError

    def subscribers(self, topic: str) -> SubscriberSet:
        raise NotImplementedError

    async def subscribers_async(self, topic: str) -> SubscriberSet:
        """Event-loop-friendly match: one topic, on a worker thread."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self.subscribers, topic)


class SigEngine(OverlayedEngine):
    """Device-resident signature matcher bound to a TopicIndex.

    The device program is grouped signature equality; a topic that
    overflows it (too deep, too many rows) is answered from the CPU trie.
    """

    def __init__(self, index: TopicIndex, max_levels: int = 16,
                 max_words: int = 32, device=None,
                 auto_refresh: bool = True,
                 compact_word_slots: int = 8, compact_max_rows: int = 16,
                 compact_cap_per_topic: int = 3,
                 fixed_sel_blocks: int = 8,
                 fixed_max_rows: int = 7,
                 use_pallas: bool | str = "auto",
                 kernel_width: str = "auto") -> None:
        self.index = index
        self.max_levels = max_levels
        self.max_words = max_words
        self.device = device
        self.auto_refresh = auto_refresh
        # compact-path shape knobs (see sig_match_compact_body): topics
        # with more than compact_word_slots nonzero words or
        # compact_max_rows matches overflow to the CPU trie; the stream
        # carries compact_cap_per_topic rows/topic on average
        if not 1 <= compact_max_rows <= 254:
            # counts_u8 reserves 255 for overflow; a larger cap would let
            # the clamped count desynchronize host stream offsets
            raise ValueError("compact_max_rows must be in [1, 254]")
        self.compact_word_slots = compact_word_slots
        self.compact_max_rows = compact_max_rows
        self.compact_cap_per_topic = compact_cap_per_topic
        # fixed-slot path shape knobs (see sig_match_fixed_body): the
        # defaults (8 blocks / 7 rows) put overflow->CPU-trie fallback at
        # the ~1% level for 100K-sub IoT corpora at 16B/topic; larger
        # corpora match more rows per topic and want larger max_rows
        # (<= 14 to keep the 4-bit count packing)
        if not 1 <= fixed_max_rows <= 14:
            # the 4-bit count packing reserves 0xF for overflow
            raise ValueError("fixed_max_rows must be in [1, 14]")
        self.fixed_sel_blocks = fixed_sel_blocks
        self.fixed_max_rows = fixed_max_rows
        # fixed path device program: True = fused Pallas kernel (error if
        # the tables exceed its VMEM plan), "auto" = kernel when it fits,
        # False = XLA body
        self.use_pallas = use_pallas
        self.pallas_active = False
        self._xla_fallback_logged = False
        # dual-width plane compare: "auto" runs packed 16-bit planes for
        # eligible groups (compile-time injective fold, see
        # _pick_fold16), "32" forces the uniform 32-bit planes
        if kernel_width not in ("auto", "32"):
            raise ValueError("kernel_width must be 'auto' or '32'")
        self.kernel_width = kernel_width
        self.kernel_plan = None    # sig_pallas.plan of the live program
        # emit DeliveryIntents (flat fan-out-ready entries, ADR 007)
        # instead of merged SubscriberSet dicts from the native decode —
        # the production broker path; falls back to sets automatically
        # for overlay windows, CPU-trie fallbacks, and when the C
        # extension is absent (consumers handle both shapes)
        self.emit_intents = False
        # auto-route TINY corpora to the CPU trie (ADR 008): a few
        # hundred subscriptions never amortize table compiles and
        # device batches; everything larger stays on the device path
        # (link-degraded regimes are the batcher's adaptive bypass)
        self.route_small = True
        self.trie_routed = 0
        self._state = None
        self._refresh_lock = threading.Lock()
        self.fallbacks = 0
        self.matches = 0
        self.host_matches = 0     # topics served by the device-free path
        # device-free batches answered by the one native call
        # (maxmq_decode.host_batch)
        self.host_fused_batches = 0
        # rows-count hint for the stream prefetch (see dispatch_fixed)
        self._stream_rows_hint = _STREAM_CHUNK
        # host seconds of the newest table compile (compile_sig, then
        # constants upload + program build)
        self.refresh_seconds: dict[str, float] = {}
        self._init_overlay()
        self.refresh(force=True)

    @staticmethod
    def _state_version(state) -> int:
        return state[0].version

    # ------------------------------------------------------------------

    def refresh(self, force: bool = False) -> bool:
        """Recompile + upload if the index changed (atomic state swap:
        a match in flight keeps the complete state it read)."""
        with self._refresh_lock:
            state = self._state
            if (not force and state is not None
                    and state[0].version == self.index.sub_version):
                return False
            faults.fire(faults.DEVICE_RECOMPILE)
            t0 = time.perf_counter()
            with self._span("maxmq.compile_sig"):
                tables = compile_sig(self.index,
                                     max_levels=self.max_levels)
            t1 = time.perf_counter()
            if len(tables.groups) > MAX_GROUPS:
                # pathological corpus (thousands of distinct wildcard
                # shapes): keep serving EXACTLY via the CPU trie rather
                # than raising on the publish hot path; recompile again
                # once the corpus changes
                self._state = (tables,) + (None,) * 6 + (False,)
                return True
            dput = lambda x: jax.device_put(jnp.asarray(x), self.device)
            consts = {
                "topo_coef": dput(tables.topo_coef),
                "depth_coef": dput(tables.depth_coef),
                "min_depth": dput(tables.min_depth),
                "is_hash": dput(tables.is_hash),
                "wild_first": dput(tables.wild_first),
                "group_words_host": tuple(int(w) for w in
                                          tables.group_words),
            }
            n_words = max(int(tables.group_words.sum()), 1)
            planes = dput(np.ascontiguousarray(
                tables.row_sig.reshape(n_words, 32).T)
                if tables.n_rows else
                np.full((32, 1), 0xFFFFFFFF, dtype=np.uint32))
            max_words = self.max_words

            @jax.jit
            def fn(toks, lengths, dollar):
                return sig_match_body(consts, planes, toks, lengths,
                                      dollar, max_words=max_words)

            @jax.jit
            def fn_many(toks, lengths, dollar):
                def step(carry, inp):
                    t, ln, d = inp
                    return carry, sig_match_body(consts, planes, t, ln, d,
                                                 max_words=max_words)
                _, out = jax.lax.scan(step, 0, (toks, lengths, dollar))
                return out

            slots, rows = self.compact_word_slots, self.compact_max_rows
            per_topic = self.compact_cap_per_topic

            @jax.jit
            def fn_compact(toks8, lens_enc):
                return sig_match_compact_body(
                    consts, planes, toks8, lens_enc, max_word_slots=slots,
                    max_rows=rows, cap=per_topic * toks8.shape[0])

            @jax.jit
            def fn_compact_many(toks8, lens_enc):
                def step(carry, inp):
                    t, le = inp
                    return carry, sig_match_compact_body(
                        consts, planes, t, le, max_word_slots=slots,
                        max_rows=rows, cap=per_topic * t.shape[0])
                _, out = jax.lax.scan(step, 0, (toks8, lens_enc))
                return out

            fn_fixed, fmt = self._build_fixed_program(tables, consts,
                                                      planes, n_words)
            self._state = (tables, consts, fn, fn_many,
                           fn_compact, fn_compact_many, fn_fixed, fmt)
            self._freeze_heap_if_large(tables)
            self.refresh_seconds = {"compile_sig": t1 - t0,
                                    "upload": time.perf_counter() - t1}
            return True

    # generational-GC hygiene for huge corpora: a compiled million-sub
    # table is several MILLION long-lived acyclic objects (Subscription
    # records, client-id strings, filter keys). Left in the normal
    # generations, every full collection walks them all — measured as a
    # recurring ~40x whole-batch decode stall (seconds) whenever the
    # allocation surplus around a decode-cache fill tripped gen2.
    # gc.freeze() moves the survivors to the permanent generation;
    # refcounting still reclaims them (the table's only cycle runs
    # through the decode capsule and is broken explicitly by
    # table_release on rotation). Frozen once per PROCESS growth step:
    # re-freezing on every rotation would progressively pin transient
    # broker state, so we freeze only when the live table is at least
    # twice as large as at the last freeze.
    GC_FREEZE_MIN_SUBS = 100_000
    _frozen_subs = 0

    def _freeze_heap_if_large(self, tables) -> None:
        try:
            n = int(self.index.subscription_count)
        except Exception:
            n = 0
        cls = SigEngine
        if n >= self.GC_FREEZE_MIN_SUBS and n >= 2 * cls._frozen_subs:
            import gc
            # On a GROWTH step everything previously frozen comes back
            # out first: cycles formed through frozen objects since the
            # last freeze (the permanent generation is never scanned)
            # become collectable again for exactly one collection, then
            # the whole surviving set re-freezes. Net effect: cycle
            # garbage among frozen objects is bounded by one growth
            # interval instead of the process lifetime (ADR 009).
            if cls._frozen_subs:
                gc.unfreeze()
            # collect before freezing: freeze() moves EVERYTHING tracked
            # into the permanent generation, including any collectable
            # cycles alive right now (e.g. a rotated-out snapshot whose
            # weakref.finalize must still fire) — those would otherwise
            # leak for the life of the process
            gc.collect()
            gc.freeze()
            cls._frozen_subs = n

    def _build_fixed_program(self, tables, consts, planes, n_words):
        """The fixed-slot device program: the fused Pallas chunk kernels
        when the VMEM plan admits the tables, else the XLA body."""
        sb, kr = self.fixed_sel_blocks, self.fixed_max_rows
        fmt16 = n_words * 32 <= 65536
        fmt = {"kind": "fmt16"} if fmt16 else {"kind": "fmt32"}
        self.pallas_active = False
        self.kernel_plan = None
        if self.use_pallas:
            from . import sig_pallas
            kplan = sig_pallas.plan(
                tables, force_width32=self.kernel_width == "32")
            if kplan is not None:
                fn_fixed, fmt = sig_pallas.build_fixed_fn(
                    tables, consts, kplan, max_rows=kr)
                self.pallas_active = True
                self.kernel_plan = kplan
                return fn_fixed, fmt
            if self.use_pallas is True:
                raise ValueError(
                    "use_pallas=True but tables exceed the kernel's "
                    "VMEM plan (use 'auto' to fall back to XLA)")
            if not self._xla_fallback_logged:
                self._xla_fallback_logged = True
                logging.getLogger("maxmq.matcher").warning(
                    "fused kernel declined, serving from the XLA body: "
                    "no batch tile fits the %d-byte VMEM budget at %d "
                    "groups / %d words", sig_pallas.VMEM_BUDGET,
                    len(tables.groups), n_words)

        @jax.jit
        def fn_fixed(toks8, lens_enc):
            return sig_match_fixed_body(consts, planes, toks8,
                                        lens_enc, sel_blocks=sb,
                                        max_rows=kr)
        return fn_fixed, fmt

    @property
    def tables(self) -> SigTables:
        return self._state[0]

    @property
    def fixed_program(self):
        """(jitted fixed-path fn, wire-format descriptor) — the public
        view of the compiled program for harnesses that dispatch the
        device half directly (the driver's compile check)."""
        return self._state[6], self._state[7]

    # ------------------------------------------------------------------

    def match_raw(self, topics: list[str]):
        """Device match of the wildcard rows + host probe of the exact
        rows. Returns (word_idx int32[B, K], word_val uint32[B, K],
        overflow bool[B], hostrows list[np.ndarray], tables)."""
        if self.auto_refresh:
            self.refresh_soon()
        state = self._state
        if state[2] is None:
            raise RuntimeError(
                "device matching disabled for this corpus "
                f"(> {MAX_GROUPS} signature groups); use the subscribers_* "
                "APIs, which fall back to the CPU trie")
        faults.fire(faults.DEVICE_MATCH)
        tables, fn = state[0], state[2]
        toks, lengths, dollar = tables.tokenize(topics, self.max_levels)
        word_idx, word_val, overflow = fn(
            jnp.asarray(toks), jnp.asarray(lengths), jnp.asarray(dollar))
        hostrows = host_exact_rows(tables, toks, lengths)
        host_plus_rows(tables, toks, lengths, np.asarray(dollar),
                       into=hostrows)
        return (np.asarray(word_idx), np.asarray(word_val),
                np.asarray(overflow), hostrows, tables)

    def match_raw_many(self, batches: list[list[str]]):
        """Match a stack of equal-sized topic batches in one device
        dispatch (a lax.scan over the stack)."""
        if self.auto_refresh:
            self.refresh_soon()
        state = self._state
        if state[2] is None:
            raise RuntimeError(
                "device matching disabled for this corpus "
                f"(> {MAX_GROUPS} signature groups); use the subscribers_* "
                "APIs, which fall back to the CPU trie")
        tables, fn_many = state[0], state[3]
        toks, lengths, dollar, hostrows = [], [], [], []
        for topics in batches:
            t, ln, d = tables.tokenize(topics, self.max_levels)
            toks.append(t)
            lengths.append(ln)
            dollar.append(d)
            hr = host_exact_rows(tables, t, ln)
            host_plus_rows(tables, t, ln, np.asarray(d), into=hr)
            hostrows.append(hr)
        word_idx, word_val, overflow = fn_many(
            jnp.asarray(np.stack(toks)), jnp.asarray(np.stack(lengths)),
            jnp.asarray(np.stack(dollar)))
        return (np.asarray(word_idx), np.asarray(word_val),
                np.asarray(overflow), hostrows, tables)

    def match_compact(self, topics: list[str]):
        """Transfer-minimal device match of one batch. Returns
        (counts uint8[B], stream uint32[cap], total int, hostrows,
        tables)."""
        if self.auto_refresh:
            self.refresh_soon()
        state = self._state
        if state[2] is None:
            raise RuntimeError(
                "device matching disabled for this corpus "
                f"(> {MAX_GROUPS} signature groups); use the subscribers_* "
                "APIs, which fall back to the CPU trie")
        tables, fn_compact = state[0], state[4]
        toks8, lens_enc, hostrows = prepare_batch(tables, topics)
        counts, stream, total = fn_compact(jnp.asarray(toks8),
                                           jnp.asarray(lens_enc))
        return (np.asarray(counts), np.asarray(stream), int(total),
                hostrows, tables)

    def match_compact_many(self, batches: list[list[str]]):
        """Transfer-minimal match of a stack of equal-sized batches in one
        device dispatch. Returns (counts uint8[I, B], stream uint32[I, cap],
        totals int32[I], hostrows list[list[np.ndarray]], tables).

        The host-exact searchsorted probe runs while the device chews on
        the wildcard rows (async dispatch overlaps them naturally)."""
        if self.auto_refresh:
            self.refresh_soon()
        state = self._state
        if state[2] is None:
            raise RuntimeError(
                "device matching disabled for this corpus "
                f"(> {MAX_GROUPS} signature groups); use the subscribers_* "
                "APIs, which fall back to the CPU trie")
        tables, fn_compact_many = state[0], state[5]
        toks, lens, hostrows = [], [], []
        for topics in batches:
            t, le, hr = prepare_batch(tables, topics)
            toks.append(t)
            lens.append(le)
            hostrows.append(hr)
        counts, stream, totals = fn_compact_many(
            jnp.asarray(np.stack(toks)), jnp.asarray(np.stack(lens)))
        return (np.asarray(counts), np.asarray(stream),
                np.asarray(totals), hostrows, tables)

    def match_fixed(self, topics: list[str], out=None):
        """Fixed-slot device match (fewest bytes / kernels; see
        sig_match_fixed_body). Returns (counts int32[B], rows uint32[B, kr]
        (0xFFFF/0xFFFFFFFF filled), hostrows, tables); count 15 = overflow.

        ``out=device_array`` skips dispatch and just unpacks a result from
        a previous ``dispatch_fixed`` (the pipelined-fetch building block).
        """
        if out is None:
            out = self.dispatch_fixed(topics)
        # unpack with the SAME snapshot the dispatch used — a concurrent
        # refresh() must never pair a new format with an old result
        out, hostrows, tables, fmt = out[:4]
        kind = fmt["kind"]
        if kind == "stream":
            cnt, real, flat = self._fetch_stream(out)
            kr = fmt["max_rows"]
            rows = np.full((len(cnt), kr), 0xFFFFFFFF, dtype=np.uint32)
            if flat is not None:
                mask = np.arange(kr, dtype=np.int64)[None, :] \
                    < real[:, None]
                rows[mask] = flat
            return cnt, rows, hostrows, tables
        o = np.asarray(out)
        if kind == "fmt16":
            cnt = (o[:, 0] >> 28).astype(np.int32)
            row16 = [o[:, 0] & 0xFFFF]
            for c in range(1, o.shape[1]):
                row16.append(o[:, c] & 0xFFFF)
                row16.append(o[:, c] >> 16)
            rows = np.stack(row16[:self.fixed_max_rows], axis=1)
        else:
            cnt = o[:, 0].astype(np.int32)
            rows = o[:, 1:1 + self.fixed_max_rows]
        return cnt, rows, hostrows, tables

    def counts_fixed(self, out):
        """Counts + host CSR of a dispatched fixed batch WITHOUT
        materializing the [B, max_rows] row matrix (pipelined raw
        consumers count matches; only decode needs rows). The stream
        format still fetches the full row stream — the honest link
        cost — it just skips the 15MB-per-batch matrix scatter."""
        out, hostrows, tables, fmt = out[:4]
        if fmt["kind"] == "stream":
            cnt, _real, _flat = self._fetch_stream(out)
            return cnt, hostrows, tables
        o = np.asarray(out)
        if fmt["kind"] == "fmt16":
            cnt = (o[:, 0] >> 28).astype(np.int32)
        else:
            cnt = o[:, 0].astype(np.int32)
        return cnt, hostrows, tables

    def _fetch_stream(self, out):
        """Fetch the stream wire format to host: (cnt int32[B] with 15 =
        overflow, real int64[B] true per-topic counts, flat uint32[total]
        topic-sorted row stream or None when empty). The counts and the
        hint-predicted front of the stream were already fetched
        asynchronously at dispatch time; only a hint shortfall costs a
        synchronous slice here. 255 = overflow sentinel -> 15."""
        counts_dev, stream_dev, slices = out
        cnt_u8 = np.asarray(counts_dev)
        cnt = np.where(cnt_u8 == 0xFF, 15, cnt_u8).astype(np.int32)
        real = np.where(cnt_u8 == 0xFF, 0, cnt_u8).astype(np.int64)
        total = int(real.sum())
        # EMA hint for the next dispatch's prefetch (~1.25x headroom)
        self._stream_rows_hint = (self._stream_rows_hint
                                  + total + total // 4) // 2
        if not total:
            return cnt, real, None
        have = sum(s.shape[0] for s in slices)
        parts = [np.asarray(s) for s in slices]
        c0 = have
        cap = stream_dev.shape[0]
        while c0 < total:
            n = min(_STREAM_CHUNK, cap - c0)
            parts.append(np.asarray(stream_dev[c0:c0 + n]))
            c0 += n
        flat = parts[0] if len(parts) == 1 else np.concatenate(parts)
        return cnt, real, flat[:total]

    def dispatch_fixed(self, topics: list[str]):
        """Tokenize + enqueue the fixed-slot match without waiting: the
        returned device array is fetched later (double-buffered pipelines
        overlap this batch's device work with the previous batch's fetch).
        """
        if self.auto_refresh:
            self.refresh_soon()
        state = self._state
        if state[2] is None:
            raise RuntimeError(
                "device matching disabled for this corpus "
                f"(> {MAX_GROUPS} signature groups); use the subscribers_* "
                "APIs, which fall back to the CPU trie")
        faults.fire(faults.DEVICE_MATCH)
        tables, fn_fixed, fmt = state[0], state[6], state[7]
        # ADR 015: the phases go into the micro-batch's record when a
        # tracing batcher handed one to this thread, else nothing
        rec = active_batch()
        if rec is not None:
            rec.begin("match_prep")
        toks8, lens_enc, hostrows = prepare_batch(tables, topics)
        # Bucket the batch axis to powers of two: fn_fixed is jitted, so
        # every DISTINCT batch shape costs a full XLA compile (seconds) —
        # fatal for the MicroBatcher, whose batch sizes vary per window.
        # Pad rows are depth-1 '$'-topics of all-pad tokens: '$' excludes
        # every wildcard-first group [MQTT-4.7.1-1/2] and no literal level
        # can equal the reserved pad token, so pads match nothing and add
        # nothing to the row stream (which is topic-sorted anyway).
        b = len(topics)
        bucket = _batch_bucket(b)
        if bucket != b:
            _dt, padval = _compact_dtype(tables)
            tp = np.full((bucket, *toks8.shape[1:]), padval,
                         dtype=toks8.dtype)
            tp[:b] = toks8
            lp = np.full(bucket, -1, dtype=lens_enc.dtype)
            lp[:b] = lens_enc
            toks8, lens_enc = tp, lp
        if rec is not None:
            rec.end()
            rec.begin("match_dispatch")
        # both fixed-path programs are jitted and device_put numpy inputs
        out = fn_fixed(toks8, lens_enc)
        if fmt["kind"] == "stream":
            # start the device->host copies NOW so they ride the link
            # while the host preps the next batch and the device chews on
            # it: counts always, plus the stream slices a rows-count hint
            # (EMA of recent batches) predicts will be needed. A short
            # hint costs one synchronous slice fetch at unpack time.
            counts_dev, stream_dev = out
            counts_dev.copy_to_host_async()
            cap = stream_dev.shape[0]
            hint = min(self._stream_rows_hint, cap)
            slices = []
            c0 = 0
            while c0 < hint or not slices:
                n = min(_STREAM_CHUNK, cap - c0)
                if n <= 0:
                    break
                s = stream_dev[c0:c0 + n]
                s.copy_to_host_async()
                slices.append(s)
                c0 += n
            out = (counts_dev, stream_dev, slices)
        if rec is not None:
            rec.end()
        return out, hostrows, tables, fmt, toks8, lens_enc

    # Auto-route (ADR 008): serve TINY corpora from the CPU trie — a
    # few hundred subscriptions never amortize table compiles and
    # device batches, and the trie answers in ~1-2us/topic at this
    # size. Anything larger stays on the device path: measured with
    # warmed buckets, the device beats the trie even on exact-only 1K
    # corpora (sets 1.44M vs trie 735K topics/s, CPU backend), and
    # a slow host link is handled by the MicroBatcher's adaptive
    # measured-RTT bypass, not a static rule.
    ROUTE_SUBS_MAX = 256

    def _routes_to_trie(self) -> bool:
        return (self.route_small
                and self.index.subscription_count <= self.ROUTE_SUBS_MAX)

    def _trie_batch(self, topics: list[str]) -> list[SubscriberSet] | None:
        """CPU-trie service for corpora the compiler declined
        (> MAX_GROUPS wildcard shapes) or the ADR-008 router claims;
        None when the device path should run."""
        if self.auto_refresh:
            self.refresh_soon()
        declined = self._state[2] is None
        if not declined and not self._routes_to_trie():
            return None
        self.matches += len(topics)
        if declined:
            self.fallbacks += len(topics)
        else:
            self.trie_routed += len(topics)
        return [self.index.subscribers(t) for t in topics]

    def subscribers_fixed_batch(self, topics: list[str]
                                ) -> list[SubscriberSet]:
        """subscribers_batch over the fixed-slot path.

        Decode is batch-vectorized: every candidate (topic, row) pair —
        device slots and host-probe hits together — is verified in ONE
        numpy pass (``verify_pairs``); the python loop then only unions
        the verified rows' entries, with no per-row filter walk. This is
        the fan-out-rate-critical half the device cannot do."""
        cpu = self._trie_batch(topics)
        if cpu is not None:
            return cpu
        try:
            ctx = self.dispatch_fixed(topics)
        except faults.DeviceMatchError:
            # a device fault is NOT the trie-only state swap below: it
            # must surface so the ADR-011 supervisor can count it toward
            # its breaker (it still answers the caller from the trie)
            raise
        except RuntimeError:     # state swapped to trie-only mid-call
            return self._resync_batch(topics)
        return self.collect_fixed(topics, ctx)

    def subscribers_host_batch(self, topics: list[str]
                               ) -> list[SubscriberSet]:
        """Device-free full match: tokenize + exact/'+' probes, the
        '#'-group host probe, then the same batch verify + union decode
        — no dispatch, no device round trip.

        Together the three probes cover every compiled group, so the
        result is exactly subscribers_fixed_batch's (same caching, same
        immutable-result contract) at a per-topic cost of a handful of
        hashed look-ups — the batcher's low-occupancy bypass serves
        from here instead of walking the CPU trie. With the native
        libraries built the whole batch is ONE call
        (``maxmq_decode.host_batch``): a batch of one pays no per-step
        glue. Overflow topics and router/declined corpora fall back to
        the trie exactly like the device path."""
        cpu = self._trie_batch(topics)
        if cpu is not None:
            return cpu
        tables = self._state[0]
        overlay = self.overlay_for(tables.version)
        if overlay == "resync":
            return self._resync_batch(topics)
        nh = _native_host(tables)
        if nh is None:
            return self._host_batch_numpy(topics, tables)
        host_batch, cap, hcap = nh
        rec = active_batch()            # ADR 015, as in dispatch_fixed
        form = (_FORM_PAIRS if overlay is not None
                else _FORM_INTENTS if self.emit_intents else _FORM_SET)
        out, fall = host_batch(cap, hcap, topics, form,
                               None if rec is None else _part_marker(rec))
        batch = len(topics)
        self.host_fused_batches += 1
        self.matches += batch
        # overflow topics are served by the trie fallback pass and
        # counted under fallbacks — not host matches
        self.host_matches += batch - len(fall)
        if overlay is not None:
            # an overlay window unions in the set form, dropping the
            # subscriptions the overlay removed (merge_delta adds its)
            ti, rw = out
            out = [SubscriberSet() for _ in range(batch)]
            _union_pairs_removed(out, ti, rw, tables, overlay.removed)
        if rec is not None:
            rec.end()
        if fall or overlay is not None:
            out = self._overlay_fallback_pass(topics, out, fall, overlay)
        return out

    def _host_batch_numpy(self, topics: list[str], tables
                          ) -> list[SubscriberSet]:
        """subscribers_host_batch where the native libraries are not
        built: the numpy probes and the python decode."""
        batch = len(topics)
        rec = active_batch()
        if rec is not None:
            rec.begin("match_prep")
        toks, lens_enc, hostrows = prepare_batch(tables, topics)
        lengths = np.abs(lens_enc.astype(np.int32))
        fall = lengths >= 127
        self.host_matches += batch - int(fall.sum())
        if rec is not None:
            rec.end()
            rec.begin("match_probe")
        # the '#' hits ride _pairs_with_host's device-pair slot
        # (hostrows may be the fused tokenizer's CSR, which
        # _scatter_hits cannot append into)
        hh = host_hash_rows(tables, toks, lengths, lens_enc < 0)
        ti_h = np.repeat(np.arange(batch), [len(h) for h in hh])
        rw_h = (np.concatenate([np.asarray(h) for h in hh])
                .astype(np.int64) if len(ti_h)
                else np.empty(0, dtype=np.int64))
        if rec is not None:
            rec.end()
            rec.begin("match_decode")
        ti, rw = _pairs_with_host(batch, ti_h, rw_h, hostrows,
                                  fall, tables)
        out = self.decode_pairs(topics, fall, ti, rw, tables, toks,
                                lens_enc)
        if rec is not None:
            rec.end()
        return out

    def collect_fixed(self, topics: list[str], ctx) -> list[SubscriberSet]:
        """Decode half of the fixed-slot path: fetch + batch-verify +
        entry union for a previously dispatched batch. The stream wire
        format skips the [B, max_rows] matrix round-trip entirely — the
        fetched stream already IS the topic-sorted device pair list."""
        out, hostrows, tables, fmt = ctx[:4]
        toks8, lens_enc = ctx[4], ctx[5]
        stream = fmt["kind"] == "stream"
        if stream and self.overlay_for(tables.version) == "resync":
            return self._resync_batch(topics)   # skip the flatten
        rec = active_batch()            # ADR 015, as in dispatch_fixed
        if rec is not None:
            rec.begin("match_fetch")    # ends with the result on the host
        if stream:
            fetched = self._fetch_stream(out)
        else:
            cnt, rows, hostrows, tables = self.match_fixed([], out=ctx)
        if rec is not None:
            rec.end()
            rec.begin("match_decode")
        if stream:
            results = self._decode_stream(topics, ctx, *fetched)
        else:
            results = self.decode_fixed(topics, cnt, rows, hostrows,
                                        tables, toks8, lens_enc)
        if rec is not None:
            rec.end()
        return results

    def _decode_stream(self, topics: list[str], ctx, cnt, real, flat):
        """Host half of the stream wire format after the fetch: pair
        assembly + batch verify + entry union. Split from collect_fixed
        so latency harnesses can time fetch and decode separately on
        the SAME path production runs."""
        _, hostrows, tables, _fmt = ctx[:4]
        batch = len(topics)
        if len(cnt) > batch:            # bucket-padded dispatch: pads
            cnt, real = cnt[:batch], real[:batch]   # carry no rows
        fall = cnt == 15
        ti_dev = np.repeat(np.arange(batch), real)
        rw_dev = (flat.astype(np.int64) if flat is not None
                  else np.empty(0, dtype=np.int64))
        ti, rw = _pairs_with_host(batch, ti_dev, rw_dev, hostrows,
                                  fall, tables)
        return self.decode_pairs(topics, fall, ti, rw, tables,
                                 ctx[4], ctx[5])

    def decode_fixed(self, topics: list[str], cnt, rows, hostrows, tables,
                     toks8, lens_enc) -> list[SubscriberSet]:
        """Pure host decode given already-fetched match results in the
        row-matrix form: batch verify + entry union. Split from
        collect_fixed so harnesses can time this stage in isolation."""
        if self.overlay_for(tables.version) == "resync":
            return self._resync_batch(topics)       # skip the flatten
        if len(cnt) > len(topics):      # bucket-padded dispatch
            cnt, rows = cnt[:len(topics)], rows[:len(topics)]
        fall = cnt == 15
        ti, rw = _candidate_pairs(len(topics), cnt, rows, hostrows, fall,
                                  tables)
        return self.decode_pairs(topics, fall, ti, rw, tables, toks8,
                                 lens_enc)

    def decode_pairs(self, topics: list[str], fall, ti, rw, tables,
                     toks8, lens_enc) -> list[SubscriberSet]:
        """Pure host decode given flattened candidate pairs: batch
        verify + entry union (one C pass when the maxmq_decode extension
        is active).

        Result contract: returned SubscriberSets may be SHARED across
        topics and calls (the C pass memoizes per verified row set, and
        the broker's match cache replays results too) — treat them as
        immutable and ``deep_copy()`` before mutating, as
        Broker._fan_out does before its one mutating hook."""
        overlay = self.overlay_for(tables.version)
        if overlay == "resync":
            return self._resync_batch(topics)
        removed = overlay.removed if overlay else None

        batch = len(topics)
        self.matches += batch
        if len(lens_enc) > batch:
            # bucket-padded dispatch: the C decode pass derives the token
            # matrix width from len/batch, so hand it exactly [batch, W]
            # (leading-axis slices of C-contiguous arrays stay contiguous)
            toks8, lens_enc = toks8[:batch], lens_enc[:batch]

        nd = _native_decode(tables) if removed is None else None
        if nd is not None:
            out = self._decode_native(nd, tables, toks8, lens_enc, batch,
                                      ti, rw, overlay)
        else:
            out = self._decode_python(tables, toks8, lens_enc, batch,
                                      ti, rw, removed)
        return self._overlay_fallback_pass(
            topics, out, np.flatnonzero(fall).tolist(), overlay)

    def _decode_native(self, nd, tables, toks8, lens_enc, batch, ti, rw,
                       overlay):
        """One C pass: verify + the whole entry union (plain inserts,
        identifier merges via the merge_subscription callback,
        shared-group maps) + the result construction — nothing left to
        walk in python. Intents mode (ADR 007) skips the merged-dict
        materialization entirely: flat borrowed-pointer entries the
        broker fans out directly. Overlay windows need merge_delta's
        set mutation, so they keep the set form until the background
        recompile lands."""
        mod, capsule = nd
        _dt, pad = _compact_dtype(tables)
        decode_fn = (mod.decode_batch_intents
                     if self.emit_intents and overlay is None
                     and hasattr(mod, "decode_batch_intents")
                     else mod.decode_batch)
        return decode_fn(
            capsule, toks8, toks8.dtype.itemsize, int(pad), lens_enc,
            batch, np.ascontiguousarray(ti), np.ascontiguousarray(rw))

    @staticmethod
    def _decode_python(tables, toks8, lens_enc, batch, ti, rw, removed):
        """Python fallback: numpy batch verify + per-pair entry union."""
        lengths = np.abs(lens_enc.astype(np.int32))
        dollar = lens_enc < 0
        dtype, pad = _compact_dtype(tables)
        toks32 = toks8.astype(np.int32)
        if dtype is not np.int32:
            toks32[toks32 == pad] = -1
        ok = verify_pairs(tables, toks32, lengths, dollar, ti, rw)
        ti, rw = ti[ok], rw[ok]
        out = [SubscriberSet() for _ in range(batch)]
        if removed is None:
            _union_pairs(out, ti, rw, tables)
        else:
            _union_pairs_removed(out, ti.tolist(), rw.tolist(), tables,
                                 removed)
        return out

    def _overlay_fallback_pass(self, topics, out, fall, overlay):
        """Overlay/fallback post-pass (``fall``: the indices of the
        topics the trie answers); the overwhelmingly common case (fresh
        tables, no overflow) returns the union output as-is."""
        if overlay is not None:
            skip = set(fall)              # fall slots get replaced
            for i, topic in enumerate(topics):
                if i not in skip:
                    out[i] = self.merge_delta(topic, out[i], overlay)
        for i in fall:
            self.fallbacks += 1
            out[i] = self.index.subscribers(topics[i])
        return out

    def _resync_batch(self, topics: list[str]) -> list[SubscriberSet]:
        """The journal no longer reaches the compiled tables (mutation
        storm): serve this batch exactly from the CPU trie while the
        background recompile catches up."""
        self.matches += len(topics)
        self.fallbacks += len(topics)
        return [self.index.subscribers(t) for t in topics]

    def subscribers_compact_batch(self, topics: list[str]
                                  ) -> list[SubscriberSet]:
        """subscribers_batch over the compact path (the production
        fan-out route when the host<->device link is narrow)."""
        cpu = self._trie_batch(topics)
        if cpu is not None:
            return cpu
        try:
            counts, stream, total, hostrows, tables = self.match_compact(topics)
        except faults.DeviceMatchError:
            raise               # surface to the ADR-011 supervisor
        except RuntimeError:     # state swapped to trie-only mid-call
            return self._resync_batch(topics)
        overlay = self.overlay_for(tables.version)
        if overlay == "resync":
            return self._resync_batch(topics)
        removed = overlay.removed if overlay else None
        out = []
        if total > stream.shape[0]:      # stream overflow: whole batch back
            self.matches += len(topics)
            self.fallbacks += len(topics)
            return [self.index.subscribers(t) for t in topics]
        off = 0
        for i, (topic, c) in enumerate(zip(topics, counts)):
            self.matches += 1
            c = int(c)
            if c == 255:
                self.fallbacks += 1
                out.append(self.index.subscribers(topic))
                continue
            result = self.decode_rows(topic, stream[off:off + c], tables,
                                      removed=removed)
            self.decode_rows(topic, hostrows[i], tables, into=result,
                             removed=removed)
            out.append(self.merge_delta(topic, result, overlay))
            off += c
        return out

    def subscribers_batch(self, topics: list[str]) -> list[SubscriberSet]:
        # Deep filters (> max_levels literal levels, compile-time
        # ``deep_rows``) can only match topics deeper than max_levels —
        # exactly the topics the tokenizer already flags as overflow — so
        # the CPU fallback below covers them with no extra check.
        cpu = self._trie_batch(topics)
        if cpu is not None:
            return cpu
        try:
            word_idx, word_val, overflow, hostrows, tables = \
                self.match_raw(topics)
        except faults.DeviceMatchError:
            raise               # surface to the ADR-011 supervisor
        except RuntimeError:     # state swapped to trie-only mid-call
            return self._resync_batch(topics)
        overlay = self.overlay_for(tables.version)
        if overlay == "resync":
            return self._resync_batch(topics)
        removed = overlay.removed if overlay else None
        out = []
        for i, topic in enumerate(topics):
            self.matches += 1
            if overflow[i]:
                self.fallbacks += 1
                out.append(self.index.subscribers(topic))
            else:
                result = self.decode(topic, word_idx[i], word_val[i],
                                     tables, removed=removed)
                self.decode_rows(topic, hostrows[i], tables, into=result,
                                 removed=removed)
                out.append(self.merge_delta(topic, result, overlay))
        return out

    # Below this corpus size a SINGLE topic's trie walk undercuts the
    # host path's ~90us fixed per-call cost (ctypes + numpy glue);
    # trie cost grows with the corpus, the fixed cost does not, so past
    # it the host path wins even for one topic (~10x at 1M subs).
    HOST_SINGLE_SUBS_MIN = 250_000

    def subscribers(self, topic: str) -> SubscriberSet:
        # single-topic surface: never the device (one topic cannot
        # amortize a round trip) — trie or host path by corpus size
        if self.index.subscription_count < self.HOST_SINGLE_SUBS_MIN:
            self.matches += 1
            return self.index.subscribers(topic)
        return self.subscribers_host_batch([topic])[0]

    def _has_program(self) -> bool:
        # a declined or ADR-008-routed corpus is served by the trie
        return self._state[2] is not None and not self._routes_to_trie()

    def _warm_one(self, size: int) -> None:
        ctx = self.dispatch_fixed(["$maxmq/warm"] * size)
        # block on the raw device output directly — going through
        # _fetch_stream would fold this zero-match batch into the
        # stream-prefetch EMA hint
        out = ctx[0]
        head = out[0] if isinstance(out, tuple) else out
        np.asarray(head)

    def prewarm_decode_bases(self, chunk: int = 2048) -> int:
        """Build the chained-decode anchors (per-row slot maps + pinned
        single-row intents) for the live table NOW, in GIL-bounded
        chunks, instead of paying the population ramp across the first
        few hundred thousand cold topics (measured ~300K topics at 1M
        subs). Production calls this at the boot quiescent point
        (bootstrap.build_matcher) and after each rotation on the
        background refresh thread. Returns the number of chunk calls
        made (0 when the intents decode is unavailable)."""
        if not self.emit_intents:
            return 0
        tables = self._state[0] if self._state else None
        if tables is None:
            return 0
        return prewarm_tables(tables, chunk)

    @staticmethod
    def _add_row(result: SubscriberSet, row: int, tables: SigTables,
                 tlevels, dollar: bool, removed=None) -> None:
        """Verify one candidate row against the topic and union its
        entries (padding bits and hash collisions are dropped here;
        ``removed`` drops pairs the overlay has unsubscribed/replaced)."""
        if row >= len(tables.row_levels):
            return                      # padding-word artifact, not a row
        flevels = tables.row_levels[row]
        if flevels is None or not filter_matches_topic(flevels, tlevels,
                                                       dollar):
            return
        entries = tables.entries
        for b in tables.row_entries[row]:
            entry = entries[b]
            if entry.shared:
                for cid, sub in entry.candidates.items():
                    if removed and (cid, sub.filter) in removed:
                        continue
                    result.add_shared(entry.group, sub.filter, cid, sub)
            else:
                sub = entry.subscription
                if removed and (entry.client_id, sub.filter) in removed:
                    continue
                result.add(entry.client_id, sub, sub.filter)

    @staticmethod
    def decode(topic: str, word_idx: np.ndarray, word_val: np.ndarray,
               tables: SigTables, into: SubscriberSet | None = None,
               removed=None) -> SubscriberSet:
        """Union matched words' rows into a SubscriberSet, re-verifying
        each row's filter against the topic (collision guard)."""
        result = SubscriberSet() if into is None else into
        tlevels = split_levels(topic)
        dollar = topic.startswith("$")
        for w, bits in zip(word_idx, word_val):
            if w < 0:
                break
            base = int(w) << 5
            bits = int(bits)
            while bits:
                low = bits & -bits
                SigEngine._add_row(result, base + low.bit_length() - 1,
                                   tables, tlevels, dollar, removed)
                bits ^= low
        return result

    @staticmethod
    def decode_rows(topic: str, rows: np.ndarray, tables: SigTables,
                    into: SubscriberSet | None = None,
                    removed=None) -> SubscriberSet:
        """Union a compact row-id slice into a SubscriberSet (verified)."""
        result = SubscriberSet() if into is None else into
        tlevels = split_levels(topic)
        dollar = topic.startswith("$")
        for row in rows:
            SigEngine._add_row(result, int(row), tables, tlevels, dollar,
                               removed)
        return result

    @staticmethod
    def merge_delta(topic: str, result: SubscriberSet,
                    overlay: Overlay | None) -> SubscriberSet:
        """Union the overlay's delta-trie matches for ``topic``."""
        if overlay is not None:
            extra = overlay.delta.subscribers(topic)
            for cid, sub in extra.subscriptions.items():
                result.add(cid, sub, sub.filter)
            for (g, f), members in extra.shared.items():
                for cid, sub in members.items():
                    result.add_shared(g, f, cid, sub)
        return result
