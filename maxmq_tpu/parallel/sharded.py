"""Mesh-sharded signature matcher: the cluster mode of the framework.

The reference's cluster design is a Route Table of topic-filter -> broker
IDs with inter-broker PUBLISH forwarding (it exists only as a design doc:
/root/reference/docs/system-design.md:201-231). TPU-native, the whole idea
collapses into sharded evaluation + one gather: partition the
*subscriptions* across the device mesh, compile one (small) signature
table per shard (matching/sig.py), let every device compare its own
table against its slice of the publish batch, and reassemble the
per-shard matched row ids. The "route lookup + forward" becomes moving a
few int32 row ids over the ICI.

Mesh axes:
  * ``data`` — data parallelism over the publish batch (each device matches
    a slice of the topics).
  * ``subs`` — the scale axis: subscriptions are partitioned by client
    hash into one table per mesh column, so 1M+ subscriptions never need
    one device's HBM. Per-shard tables are padded to identical shapes and
    stacked on a leading axis sharded over 'subs'.

Outputs are per-shard fixed match slots (out_spec P('subs', 'data', None)):
the global result [sp, B, 1 + max_rows] stays sharded on device and the
gather rides the ICI lazily when the host fetches it. Row ids are local to
their shard; the host decodes via the matching shard's row tables
(SubscriberSet union is shard-order independent).
"""

from __future__ import annotations

import threading
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..matching.sig import OverlayedEngine
from ..matching.topics import batch_bucket
from ..matching.trie import SubscriberSet, TopicIndex, subs_version


def make_mesh(shape: tuple[int, int] = None, devices=None) -> Mesh:
    """Build a ('data', 'subs') mesh over the available devices.

    Default shape: put everything on 'subs' (the scale axis) until there
    are >=8 devices, then split 2 x N/2.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if shape is None:
        shape = (2, n // 2) if n >= 8 and n % 2 == 0 else (1, n)
    mesh_devices = np.asarray(devices[: shape[0] * shape[1]]).reshape(shape)
    return Mesh(mesh_devices, axis_names=("data", "subs"))


def _pad_and_stack_shards(shards, sp: int) -> tuple:
    """Pad per-shard sig tables to common shapes and stack on 'subs'.

    +1 group column: padding word slots must NOT alias a real group — a
    real group's adjusted signature can (adversarially, the hash seed is
    deterministic) equal the 0xFFFFFFFF poison plane, emitting row ids
    past the shard's row tables. The extra all-zero-coefficient group
    has signature 0 for every topic (never the poison), so padding
    words can never fire."""
    g_real = max(max(len(t.groups), 1) for t in shards)
    g_max = g_real + 1
    g_pad = g_real
    d_max = max(max(t.probe_depth, 1) for t in shards)
    w_max = max(max(int(t.group_words.sum()), 1) for t in shards)

    topo = np.zeros((sp, g_max, d_max), dtype=np.uint32)
    dc = np.zeros((sp, g_max), dtype=np.uint32)
    mind = np.zeros((sp, g_max), dtype=np.int32)
    ish = np.zeros((sp, g_max), dtype=bool)
    wild = np.zeros((sp, g_max), dtype=bool)
    planes = np.full((sp, 32, w_max), 0xFFFFFFFF, dtype=np.uint32)
    grp = np.full((sp, w_max), g_pad, dtype=np.int32)
    for s, t in enumerate(shards):
        g = len(t.groups)
        if g:
            topo[s, :g, :t.topo_coef.shape[1]] = t.topo_coef
            dc[s, :g] = t.depth_coef
            mind[s, :g] = t.min_depth
            ish[s, :g] = t.is_hash
            wild[s, :g] = t.wild_first
        w = int(t.group_words.sum())
        if w:
            planes[s, :, :w] = t.row_sig.reshape(w, 32).T
            grp[s, :w] = np.repeat(
                np.arange(g, dtype=np.int32), t.group_words)
    return (topo, dc, mind, ish, wild, planes, grp), d_max


def _group_by_slice(devices, n_slices) -> list[list]:
    """Group devices by hardware slice_index; a synthetic even split
    when the platform reports one slice but n_slices is forced."""
    groups: dict[int, list] = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", 0) or 0, []).append(d)
    if len(groups) == 1 and n_slices and n_slices > 1:
        per = len(devices) // n_slices
        if per == 0:
            raise ValueError(f"need >= {n_slices} devices for "
                             f"{n_slices} slices, have {len(devices)}")
        groups = {i: devices[i * per:(i + 1) * per]
                  for i in range(n_slices)}
    elif n_slices and n_slices != len(groups):
        raise ValueError(f"n_slices={n_slices} but the platform reports "
                         f"{len(groups)} hardware slice(s)")
    return [groups[k] for k in sorted(groups)]


def make_multislice_mesh(n_slices: int | None = None,
                         shape: tuple[int, int] | None = None,
                         devices=None) -> Mesh:
    """('slice', 'data', 'subs') mesh for multi-slice deployments.

    Devices group by their hardware ``slice_index`` so the 'data'/'subs'
    axes always sit INSIDE a slice (collective-free matching over ICI
    neighbours); the leading 'slice' axis spans the DCN. The sharded
    engines partition subscriptions over ('slice', 'subs') jointly, and
    nothing in the match program communicates across 'slice' — matched
    rows stay slice-local until the host fetch, so the slow inter-slice
    fabric carries only result bytes, never compare traffic (the
    scaling-book recipe: keep collectives on ICI, let DCN carry the
    embarrassingly-parallel axis).

    ``n_slices`` forces a synthetic split when the platform reports a
    single slice (CPU meshes in tests; single-slice dev boxes).
    """
    import warnings

    if devices is None:
        devices = list(jax.devices())
    slices = _group_by_slice(devices, n_slices)
    per = min(len(s) for s in slices)
    if shape is None:
        shape = (1, per)
    dp, sp = shape
    if dp * sp > per:
        raise ValueError(f"per-slice shape {shape} needs {dp * sp} "
                         f"devices; smallest slice has {per}")
    idle = sum(len(s) - dp * sp for s in slices)
    if idle:
        warnings.warn(f"make_multislice_mesh leaves {idle} device(s) "
                      f"idle (unequal slices, or shape {shape} smaller "
                      "than a slice)", stacklevel=2)
    mesh_devices = np.stack([np.asarray(s[: dp * sp]).reshape(dp, sp)
                             for s in slices])
    return Mesh(mesh_devices, axis_names=("slice", "data", "subs"))


def compile_sig_shards(subs, n_shards: int, version: int,
                       by_client: bool = True):
    """Partition subscriptions BY CLIENT (stable crc32 hash of client id)
    and compile one signature table per shard with a shared token-intern
    pool (uniform token ids across the mesh, so topics are tokenized once
    and replicated over 'subs').

    Client-hash partitioning is the load-bearing choice: every entry of
    one client lives on exactly ONE shard, so per-shard decode results
    are disjoint by construction and the host can CHAIN them per topic
    (ChainedIntents) with no cross-shard merge — the sharded equivalent
    of ADR 007's no-merged-dict rule. IoT corpora carry ~1 subscription
    per client, so balance matches round-robin to within hash noise.
    ``by_client=False`` restores round-robin (the refresh fallback when
    one heavy client's wildcard shapes overflow a bucket's MAX_GROUPS —
    spreading keeps the device path alive at the cost of chaining)."""
    import zlib

    from ..matching.sig import compile_sig_subscriptions

    vocab: dict[str, int] = {}
    if by_client:
        buckets: list[list] = [[] for _ in range(n_shards)]
        for entry in subs:
            cid = entry[1]              # (filter, client_id, sub, group)
            buckets[zlib.crc32(cid.encode()) % n_shards].append(entry)
    else:
        buckets = [subs[i::n_shards] for i in range(n_shards)]
    return [compile_sig_subscriptions(b, version, vocab=vocab)
            for b in buckets]


def _sharded_sig_match(tables_dev, toks, lens_enc, *, sel_blocks, max_rows):
    """Runs INSIDE shard_map: this device's signature-table shard (leading
    axis of length 1, squeezed) over the local batch slice."""
    from ..matching.sig import (fixed_slots_from_words,
                                sig_match_words_gather)

    topo_coef, depth_coef, min_depth, is_hash, wild_first, planes, grp = (
        t[0] for t in tables_dev)
    consts = {"topo_coef": topo_coef, "depth_coef": depth_coef,
              "min_depth": min_depth, "is_hash": is_hash,
              "wild_first": wild_first}
    dollar = lens_enc < 0
    lengths = jnp.abs(lens_enc.astype(jnp.int32))
    too_deep = lengths >= 127
    words = sig_match_words_gather(consts, planes, grp,
                                   toks.astype(jnp.int32), lengths, dollar)
    out = fixed_slots_from_words(words, too_deep, sel_blocks, max_rows,
                                 fmt16=False)
    return out[None]                      # re-add the 'subs' axis


def sharded_sig_program(mesh: Mesh, subs_axes: tuple, sel_blocks: int,
                        max_rows: int):
    """The jitted cluster-mode step: ``fn(tables, toks, lens_enc)`` with
    the seven stacked shard tables partitioned over ``subs_axes`` and
    the batch over 'data'; out [sp, B, 1 + max_rows] stays sharded."""
    return jax.jit(jax.shard_map(
        partial(_sharded_sig_match, sel_blocks=sel_blocks,
                max_rows=max_rows),
        mesh=mesh,
        in_specs=(tuple(P(subs_axes) for _ in range(7)),
                  P("data"), P("data")),
        out_specs=P(subs_axes, "data", None),
    ))


def _shard_pairs(out_s, hr, batch, col, fall):
    """One shard's UNVERIFIED candidate (topic, row) pairs: device slots
    + host-probe rows, with overflowed (trie-served) topics' pairs
    dropped before the C verify."""
    cnt = out_s[:, 0].astype(np.int64)
    cnt = np.where(cnt == 0xF, 0, cnt)          # fall slots replaced later
    mask = col[None, :] < cnt[:, None]
    ti_dev = np.repeat(np.arange(batch), cnt)
    rw_dev = out_s[:, 1:][mask].astype(np.int64)
    offs = getattr(hr, "offsets", None)
    if offs is not None:                        # HostRows CSR
        ti_h = np.repeat(np.arange(batch), np.diff(offs[:batch + 1]))
        rw_h = hr.rows[:offs[batch]].astype(np.int64)
    else:
        ti_h = np.repeat(np.arange(batch), [len(h) for h in hr])
        rw_h = (np.concatenate([np.asarray(h) for h in hr])
                .astype(np.int64) if len(ti_h)
                else np.empty(0, dtype=np.int64))
    ti = np.concatenate([ti_dev, ti_h])
    rw = np.concatenate([rw_dev, rw_h])
    if fall.any():                  # overflowed topics are served by the
        keep = ~fall[ti]            # trie; don't union their pairs
        ti, rw = ti[keep], rw[keep]
    return np.ascontiguousarray(ti), np.ascontiguousarray(rw)


class ChainedIntents:
    """Per-topic cluster-mode delivery result: the per-shard
    DeliveryIntents chained, NOT merged. Valid because subscriptions
    partition by client hash (compile_sig_shards) — one client's entries
    live on exactly one shard, so the chained iteration can never name a
    client twice and no cross-shard per-client merge exists to do.
    Duck-types the ADR-007 consumer surface (__iter__/n/__len__/shared/
    resolve/to_set); shared-group candidate maps MAY span shards (a
    group's members hash apart), so ``shared`` is a lazy outer-merged
    view. Immutable, like every cached match result."""

    __slots__ = ("parts", "_shared", "_set")

    def __init__(self, parts: list) -> None:
        self.parts = parts
        self._shared = None
        self._set = None

    def __iter__(self):
        for p in self.parts:
            yield from p

    @property
    def n(self) -> int:
        return sum(p.n for p in self.parts)

    def __len__(self) -> int:
        return sum(len(p) for p in self.parts)

    @property
    def shared(self) -> dict:
        if self._shared is None:
            merged: dict = {}
            for p in self.parts:
                if len(p) == p.n:        # no shared members on this shard
                    continue
                for key, members in p.shared.items():
                    cur = merged.get(key)
                    if cur is None:
                        merged[key] = members
                    else:                # group spans shards: union view
                        cur = dict(cur)
                        cur.update(members)
                        merged[key] = cur
            self._shared = merged
        return self._shared

    def resolve(self, registry: dict,
                kept: dict | None = None) -> tuple[list, dict, int, int]:
        """``SubscriberSet.resolve`` over the chained parts: each shard
        resolves its own entries; a $share key that survives on any
        shard keeps the MERGED member map (the rotation indexes the
        group's full candidate set, which may span shards). ``kept``
        (the registry's $share counts, one map a key) is not used: a
        group that spans shards has a map a shard, and the merged map
        is this result's own, so a chained result counts as before."""
        pairs: list = []
        keys: set = set()
        matched = resolved = 0
        for p in self.parts:
            pp, cut, m, r = p.resolve(registry)
            pairs += pp
            keys.update(cut)
            matched += m
            resolved += r
        shared = ({k: v for k, v in self.shared.items() if k in keys}
                  if keys else {})
        return pairs, shared, matched, resolved

    def to_set(self) -> SubscriberSet:
        if self._set is None:
            subs: dict = {}
            for cid, sub in self:
                subs[cid] = sub          # disjoint by construction
            self._set = SubscriberSet(subs, dict(self.shared))
        return self._set


class ShardedSigEngine(OverlayedEngine):
    """Signature matcher sharded over a ('data', 'subs') mesh — cluster
    mode of the production `sig` path.

    Subscriptions partition by CLIENT HASH over 'subs'
    (compile_sig_shards — the invariant ChainedIntents' merge-free
    chaining rests on; refresh falls back to round-robin, chaining off,
    if a heavy client overflows a bucket): each device holds one
    shard's group constants + row-signature planes and matches the full
    topic batch slice against them; per-shard fixed match slots come back
    over the ICI and the host unions shard-local decodes (the reference's
    Route-Table-lookup-plus-forward collapsed into one sharded compare +
    gather, docs/system-design.md:201-231).
    """

    def __init__(self, index: TopicIndex, mesh: Mesh | None = None,
                 sel_blocks: int = 8, max_rows: int = 7) -> None:
        if not 1 <= max_rows <= 14:
            # the 4-bit count packing reserves 0xF for overflow
            raise ValueError("max_rows must be in [1, 14]")
        self.index = index
        self.mesh = mesh if mesh is not None else make_mesh()
        self.sel_blocks = sel_blocks
        self.max_rows = max_rows
        self._bind_mesh_axes()
        self._state = None
        self._refresh_lock = threading.Lock()
        self.matches = 0
        self.fallbacks = 0
        self.host_matches = 0     # topics served by the device-free path
        # cluster-mode ADR 007: per-shard native DeliveryIntents chained
        # per topic (client-hash sharding makes chaining merge-free)
        self.emit_intents = False
        self._init_overlay()
        self.refresh(force=True)

    @staticmethod
    def _state_version(state) -> int:
        return state[0]

    def _bind_mesh_axes(self) -> None:
        """Subscriptions partition over ('slice', 'subs') jointly on a
        multi-slice mesh (make_multislice_mesh) and over 'subs' on the
        plain 2-axis mesh; the match program never communicates across
        either axis, so the slice axis may ride the DCN for free."""
        names = self.mesh.axis_names
        self._subs_axes = tuple(a for a in ("slice", "subs") if a in names)
        self.sp = 1
        for a in self._subs_axes:
            self.sp *= self.mesh.shape[a]
        self.dp = self.mesh.shape["data"]

    # ------------------------------------------------------------------

    def refresh(self, force: bool = False) -> bool:
        """Re-partition + recompile + re-shard if the index changed."""
        with self._refresh_lock:
            state = self._state
            if (not force and state is not None
                    and state[0] == subs_version(self.index)):
                return False
            version = subs_version(self.index)
            shards, chain_ok = self._compile_shards(version)
            if shards is None or chain_ok is None:
                # pathological corpus under EITHER partitioning: serve
                # exactly via the CPU trie (as SigEngine.refresh)
                self._state = (version, shards or [], None, None, 0, {},
                               self.dp, False)
                return True

            stacked, d_max = _pad_and_stack_shards(shards, self.sp)
            mesh = self.mesh
            subs_axes = self._subs_axes
            by_shard = NamedSharding(mesh, P(subs_axes))
            dev = tuple(jax.device_put(a, by_shard) for a in stacked)

            fn = sharded_sig_program(mesh, subs_axes, self.sel_blocks,
                                     self.max_rows)
            # exact-group coefficients are deterministic by shape, so the
            # union over shards gives ONE esig per topic valid everywhere
            union_exact = {}
            for t in shards:
                union_exact.update(t.host_exact or {})
            # dp and chain_ok ride in the state tuple: a concurrent
            # match must pad with the SAME data-axis factor the compiled
            # fn expects, and chaining must pair atomically with the
            # partitioning that makes it merge-free, even while
            # reshard()/refresh() swap states
            self._state = (version, shards, dev, fn, d_max, union_exact,
                           self.dp, chain_ok)
            return True

    def _compile_shards(self, version: int):
        """Compile per-shard tables: client-hash first (chaining ok);
        round-robin fallback when a heavy client overflows a bucket's
        MAX_GROUPS (spreads shapes across shards, keeping the DEVICE
        path alive at the cost of merge-free chaining); (None, None)
        when even round-robin overflows."""
        from ..matching.sig import MAX_GROUPS

        subs = self.index.all_subscriptions()
        shards = compile_sig_shards(subs, self.sp, version)
        if all(len(t.groups) <= MAX_GROUPS for t in shards):
            return shards, True
        shards = compile_sig_shards(subs, self.sp, version,
                                    by_client=False)
        if all(len(t.groups) <= MAX_GROUPS for t in shards):
            return shards, False
        return None, None

    # ------------------------------------------------------------------

    def _has_program(self) -> bool:
        return (self._state[3] is not None
                and self.index.subscription_count > 0)

    def _warm_one(self, size: int) -> None:
        self.match_raw(["$maxmq/warm"] * size)      # fetches: blocks

    def prewarm_decode_bases(self, chunk: int = 2048) -> int:
        """Cluster form of SigEngine.prewarm_decode_bases: populate the
        chained-decode anchors for every SHARD's table at a quiescent
        point (called by the boot path and the background refresh).
        Skipped when the shards compiled via the round-robin
        fallback (chain_ok False, state[7]) — the intents decode never
        runs there, so anchors would be pinned dead weight. Returns
        total chunk calls made."""
        if not self.emit_intents or not self._state:
            return 0
        shards, chain_ok = self._state[1], self._state[7]
        if not shards or not chain_ok:
            return 0
        from ..matching.sig import prewarm_tables
        return sum(prewarm_tables(t, chunk) for t in shards)

    def match_raw(self, topics: list[str]):
        """Sharded device match. Returns (out uint32[sp, B, 1+max_rows],
        hostrows list[sp][B], shards, toks[B, W], lens_enc[B]),
        batch-trimmed; toks/lens_enc feed the per-shard native decode."""
        from ..matching.sig import (host_exact_rows_from_sig,
                                    host_plus_rows, prepare_batch_sig)

        self.refresh_soon()
        (_version, shards, dev, fn, d_max, union_exact, dp,
         _chain_ok) = self._state
        if fn is None:
            raise RuntimeError(
                "device matching disabled for this corpus (> MAX_GROUPS "
                "wildcard shapes in a shard); use subscribers_*, which "
                "fall back to the CPU trie")
        batch = len(topics)
        # the shared bucket ladder (ADR 006), as SigEngine.dispatch_fixed:
        # the program is jitted, so every DISTINCT batch shape is a full
        # XLA compile, and micro-batch sizes vary per window
        padded = -(-batch_bucket(batch) // dp) * dp
        padded_topics = topics + ["\x01pad"] * (padded - batch)
        # shared intern pool => identical tokens for every shard; one host
        # tokenize pass serves every shard's exact + '+'-shape probes
        toks, lens_enc, esig, lengths = prepare_batch_sig(
            shards[0], padded_topics, window=max(d_max, 1),
            host_exact=union_exact)
        out = fn(dev, jnp.asarray(toks), jnp.asarray(lens_enc))
        dollar = lens_enc < 0
        hostrows = []
        for t in shards:
            hr = host_exact_rows_from_sig(t, esig, lengths)
            host_plus_rows(t, toks, lengths, dollar, into=hr)
            hostrows.append(hr)
        return (np.asarray(out)[:, :batch],
                [h[:batch] for h in hostrows], shards,
                toks[:batch], lens_enc[:batch])

    def _trie_all(self, topics: list[str]) -> list[SubscriberSet]:
        self.matches += len(topics)
        self.fallbacks += len(topics)
        return [self.index.subscribers(t) for t in topics]

    def subscribers_batch(self, topics: list[str]) -> list[SubscriberSet]:
        self.refresh_soon()
        if self._state[3] is None:      # pathological corpus: CPU trie
            return self._trie_all(topics)
        try:
            out, hostrows, shards, toks, lens_enc = self.match_raw(topics)
        except RuntimeError:            # state swapped to disabled mid-call
            return self._trie_all(topics)
        overlay = self.overlay_for(shards[0].version)
        if overlay == "resync":
            return self._trie_all(topics)
        if self.emit_intents and overlay is None and self._state[7]:
            chained = self._decode_intents(topics, out, hostrows, shards,
                                           toks, lens_enc)
            if chained is not None:
                return chained
        return self._decode_sets(topics, out, hostrows, shards, overlay)

    def _decode_sets(self, topics, out, hostrows, shards, overlay):
        """Per-topic python union across shards (the set form; also the
        overlay-window path, which needs merge_delta's mutation)."""
        from ..matching.sig import SigEngine

        removed = overlay.removed if overlay else None
        results = []
        for i, topic in enumerate(topics):
            self.matches += 1
            cnt = out[:, i, 0]
            if (cnt == 0xF).any():
                self.fallbacks += 1
                results.append(self.index.subscribers(topic))
                continue
            result = SubscriberSet()
            for s, tables in enumerate(shards):
                SigEngine.decode_rows(topic, out[s, i, 1:1 + int(cnt[s])],
                                      tables, into=result, removed=removed)
                SigEngine.decode_rows(topic, hostrows[s][i], tables,
                                      into=result, removed=removed)
            results.append(SigEngine.merge_delta(topic, result, overlay))
        return results

    def _decode_intents(self, topics, out, hostrows, shards, toks,
                        lens_enc):
        """Cluster-mode ADR 007: one native decode_batch_intents pass PER
        SHARD (verify + union + row-set caching in C against that
        shard's table), then chain the per-shard results per topic —
        client-hash sharding guarantees disjointness. None when any
        shard lacks the native extension (python set path serves)."""
        from ..matching.sig import _compact_dtype, _native_decode

        nds = [_native_decode(t) for t in shards]
        if any(nd is None for nd in nds):
            return None
        batch = len(topics)
        self.matches += batch
        fall = (out[:, :, 0] == 0xF).any(axis=0)
        max_rows = out.shape[2] - 1
        col = np.arange(max_rows)
        per_shard: list = []
        toks = np.ascontiguousarray(toks)
        lens_enc = np.ascontiguousarray(lens_enc)
        for s, (tables, nd) in enumerate(zip(shards, nds)):
            mod, cap = nd
            ti, rw = _shard_pairs(out[s], hostrows[s], batch, col, fall)
            _dt, pad = _compact_dtype(tables)
            per_shard.append(mod.decode_batch_intents(
                cap, toks, toks.dtype.itemsize, int(pad), lens_enc,
                batch, ti, rw))
        results: list = []
        fall_list = fall.tolist()
        for i, topic in enumerate(topics):
            if fall_list[i]:
                self.fallbacks += 1
                results.append(self.index.subscribers(topic))
            else:
                results.append(ChainedIntents([ps[i] for ps in per_shard]))
        return results

    def subscribers_host_batch(self, topics: list[str]
                               ) -> list[SubscriberSet]:
        """Cluster-mode device-free match: one tokenize pass (shared
        intern pool), per-shard exact/'+'/'#' host probes, then the
        same per-shard native decode + merge-free chaining the device
        path uses — no mesh dispatch at all. Serves the batcher's
        low-occupancy bypass when a sharded engine backs the broker,
        exactly like SigEngine.subscribers_host_batch single-node."""
        from ..matching.sig import (_native_hash_probe, _scatter_hits,
                                    host_exact_rows_from_sig,
                                    host_hash_rows, host_plus_rows,
                                    prepare_batch_sig)

        self.refresh_soon()
        state = self._state
        (_version, shards, _dev, fn, d_max, union_exact, _dp,
         _chain_ok) = state
        if fn is None:                  # pathological corpus: CPU trie
            return self._trie_all(topics)
        batch = len(topics)
        toks, lens_enc, esig, lengths = prepare_batch_sig(
            shards[0], topics, window=max(d_max, 1),
            host_exact=union_exact)
        dollar = lens_enc < 0
        over = lengths < 0    # prepare_batch_sig reports overflow as -1
        toks_c = np.ascontiguousarray(toks)
        hostrows = []
        for t in shards:
            hr = host_exact_rows_from_sig(t, esig, lengths)
            host_plus_rows(t, toks, lengths, dollar, into=hr)
            # '#'-probe: the cached C ge-depth probe when built (small
            # batches are this path's whole point), numpy twin otherwise
            hp = _native_hash_probe(t)
            if hp is not None:
                ti_h, rw_h = hp.run(toks_c, lens_enc)
                if len(ti_h):
                    _scatter_hits(hr, [ti_h], [rw_h.astype(np.int64)])
            else:
                host_hash_rows(t, toks, lengths, dollar, into=hr)
            hostrows.append(hr)
        # synthesized zero-count device matrix: every candidate rides
        # the host-rows slot; overflow topics get the 0xF marker so
        # the shared decode paths serve them from the trie
        out = np.zeros((len(shards), batch, 1 + self.max_rows),
                       dtype=np.uint32)
        out[:, over, 0] = 0xF
        overlay = self.overlay_for(shards[0].version)
        if overlay == "resync":
            return self._trie_all(topics)
        # fallback-served topics (overflow now, resync above) are
        # counted under matches/fallbacks, not host matches
        self.host_matches += batch - int(over.sum())
        if self.emit_intents and overlay is None and state[7]:
            chained = self._decode_intents(topics, out, hostrows,
                                           shards, toks, lens_enc)
            if chained is not None:
                return chained
        return self._decode_sets(topics, out, hostrows, shards, overlay)

    def subscribers(self, topic: str) -> SubscriberSet:
        return self.subscribers_batch([topic])[0]

    def reshard(self, mesh: Mesh) -> None:
        """Elastic recovery: re-partition + recompile over a NEW mesh
        (e.g. after losing devices). Matching stays exact throughout —
        callers racing the swap use whichever complete state they hold,
        and the state tuple pairs shards with their compiled fn
        atomically (the reference's cluster design has no live story for
        this; its Route Table rebuild is the moral equivalent,
        docs/system-design.md:201-231)."""
        with self._refresh_lock:
            self.mesh = mesh
            self._bind_mesh_axes()
        self.refresh(force=True)
