"""Fused Pallas kernels for the signature matcher's fixed-slot path.

The XLA formulation (sig.py:sig_match_fixed_body) materializes the [B, W]
match-word matrix in HBM and re-reads it for extraction — ~2 full HBM
passes plus separate kernels for the summary/top_k/gather chain. The
kernels here fuse the per-tile pipeline in VMEM:

    one-hot MXU expansion of group signatures to words
      -> bit-plane compares -> packed words            (never leave VMEM)
      -> single-bit word encodings -> max_rows min-extract iterations
      -> per-chunk fixed candidate slots

HBM traffic collapses to the tiny inputs ([B, G] split signatures) and
the few-bytes-per-topic outputs; there is no [B, W] buffer at all, which
also removes the single-chip batch-size wall at 1M subscriptions (the
XLA path needs ~11 GB for the word matrix at batch 256K).

Scaling (vs the round-1 kernel, which kept the whole [G, W] one-hot and
a [TB, W] working set resident and therefore declined beyond ~100K
subscriptions): the word axis is split into chunks of at most
``CHUNK_WORDS`` columns, one pallas_call per chunk — all inside a SINGLE
jit (one device dispatch per batch, whatever the chunk count). Every
chunk's constants (one-hot slice + plane slice) and working set fit VMEM
regardless of corpus size; a final XLA merge sorts the per-chunk
candidates into the packed fixed-slot output. Chunk count grows linearly with the corpus; nothing
else does.

Dual-width planes (round 6): the round-5 roofline proved this kernel is
VPU compare-bound, not HBM-bound (~314 B/topic vs ~377K int-ops/topic at
1M subs), so the compare loop itself is the wall. Groups whose
signatures admit an injective 16-bit fold (sig.py:_pick_fold16 — the
compile-time meaning of "signatures fit 16 bits") are laid out after the
32-bit groups and compared against PACKED plane tables: one uint32 plane
word carries TWO rows' folded signatures (rows base+j low half,
base+16+j high half), and a SWAR zero-lane detect turns one pass over
[TB, C] into two rows' match bits — 16 plane passes per 32 rows instead
of 32, and half the plane-constant traffic. Chunks are single-width
(the two word regions are contiguous by construction), so each
pallas_call runs either the 32-bit or the packed-16 compare, never a
mixed one. ``plan(..., force_width32=True)`` builds the uniform 32-bit
program from the same compiled tables (``SigEngine(kernel_width="32")``).

Extraction rides a structural fact of the grouping: one word holds 32
rows of a SINGLE group, and within a group a topic can match at most one
row (two same-shape filters matching the same topic would be the same
filter), so >1 bit in a match word can only be a hash collision. The
kernel flags those topics as overflow (count 0xF -> exact CPU-trie
fallback; a ~2^-32 event on 32-bit planes, ~rows/2^16 per topic on
16-bit ones — which is why eligibility is bounded and per-group), which
lets the candidate bit index come from one count-leading-zeros op
instead of a popcount chain.

Exactness notes:
  * the expansion rides the MXU in f32, so the uint32 signature is split
    into 16-bit halves (both exact in f32) and recombined in-kernel; a
    16-bit group's replicated fold has equal halves, so the same split
    is trivially exact for it;
  * padding words have an all-zero one-hot column (sig_exp == 0) and
    poison planes (0xFFFFFFFF; 16-bit lanes 0xFFFF, which no eligible
    row's fold equals), so they never match;
  * the packed compare's SWAR borrow can fake a high-lane hit ONLY when
    the low lane truly matched — the word then has >=2 bits, lands in
    ``multi`` and overflows to the exact CPU fallback (a perf event,
    never a correctness event, like every collision here);
  * output format and semantics match sig_match_fixed_body with
    ``sel_blocks`` unconstrained (the kernels min-extract over the full
    width, so "matches spread over too many blocks" cannot overflow);
    the only extra overflow source is the collision case above, which
    the CPU fallback serves exactly.

Parity surface: tests/test_sig_parity.py runs every corpus through this
kernel (both widths) against the CPU trie.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .sig import SigTables, adjusted_signatures

LANE = 128
CHUNK_WORDS = 2048               # word columns per chunk kernel (2048 at
                                 # tb=128 empirically beats wider chunks
                                 # at smaller tb on v5e)
VMEM_BUDGET = 10 * 1024 * 1024   # soft per-call budget (VMEM ~16MB/core)
WORK_BUFS = 8                    # live [tb, chunk] buffers at peak


def width16_mask(tables: SigTables,
                 force_width32: bool = False) -> np.ndarray:
    """Per-group 16-bit eligibility as the planner sees it: the
    compiled ``group_w16`` when it aligns with ``group_words`` (plan
    tests override group_words to probe VMEM bounds — a misaligned
    table set is treated as all-32-bit), all-False when forced."""
    n = len(tables.group_words)
    w16 = getattr(tables, "group_w16", None)
    if force_width32 or w16 is None or len(w16) != n:
        return np.zeros(n, dtype=bool)
    return np.asarray(w16, dtype=bool)


def _region_chunk(chunk: int, region_pad: int) -> tuple[int, int]:
    """(chunk width, chunk count) for one word region: capped at the
    region itself, so a small region next to a large one never inherits
    the large region's chunk and burns compare passes on poison padding
    columns (smaller chunks only shrink the VMEM working set, so the
    planner's budget bound still holds)."""
    if not region_pad:
        return 0, 0
    c = min(chunk, region_pad)
    return c, -(-region_pad // c)


def plan(tables: SigTables, force_width32: bool = False) -> dict | None:
    """Kernel shape plan for a compiled table set, or None when no batch
    tile fits the VMEM budget (the engine then uses the XLA body —
    correctness is identical either way). The plan always succeeds for
    realistic corpora: chunk width is fixed, so per-chunk VMEM use is
    independent of the corpus size.

    The plan is mixed-width by default: the contiguous 32-bit and
    packed-16-bit word regions each get their own chunk sequence.
    ``force_width32`` plans the SAME tables as uniform 32-bit planes
    (the A/B arm); eligibility never changes the compiled layout, only
    which plane tables the chunks compare against."""
    gw = np.asarray(tables.group_words, dtype=np.int64)
    w16 = width16_mask(tables, force_width32)
    n_words32 = int(gw[~w16].sum())
    n_words16 = int(gw[w16].sum())
    if n_words32 + n_words16 == 0:
        n_words32 = 1                    # one poison word, as before
    n_words = n_words32 + n_words16
    n_groups = max(len(tables.groups), 1)
    w32_pad = -(-n_words32 // LANE) * LANE if n_words32 else 0
    w16_pad = -(-n_words16 // LANE) * LANE if n_words16 else 0
    w_pad = w32_pad + w16_pad
    g_pad = -(-n_groups // 8) * 8
    chunk = min(max(w32_pad, w16_pad), CHUNK_WORDS)

    def const_bytes(c):
        # double-buffered constants (one-hot f32 + planes u32) per call;
        # sized for the 32-bit plane table — the packed 16-bit table is
        # half of it, so this stays a safe bound for both widths
        return 2 * c * 4 * (32 + g_pad)

    # group-heavy corpora (g_pad up to MAX_GROUPS) shrink the chunk so
    # the per-call constants still fit, instead of declining
    while chunk > LANE and const_bytes(chunk) + 8 * WORK_BUFS * chunk * 4 \
            > VMEM_BUDGET:
        chunk //= 2
    chunk32, n_chunks32 = _region_chunk(chunk, w32_pad)
    chunk16, n_chunks16 = _region_chunk(chunk, w16_pad)
    n_chunks = n_chunks32 + n_chunks16
    per_row = WORK_BUFS * chunk * 4
    tb = 8
    while tb * 2 <= 128 and const_bytes(chunk) + tb * 2 * per_row \
            <= VMEM_BUDGET:
        tb *= 2
    if const_bytes(chunk) + tb * per_row > VMEM_BUDGET:
        return None
    return {"n_words": n_words, "w_pad": w_pad, "g_pad": g_pad,
            "chunk": chunk, "n_chunks": n_chunks, "tb": tb,
            # dual-width shape (32-bit words lead the row layout)
            "n_words32": n_words32, "n_words16": n_words16,
            "chunk32": chunk32, "chunk16": chunk16,
            "n_chunks32": n_chunks32, "n_chunks16": n_chunks16,
            "groups32": int((~w16).sum()), "groups16": int(w16.sum()),
            "force_width32": force_width32,
            # the compare-bound side of the roofline: plane passes over
            # [B, chunk] columns per topic (the packed compare halves
            # the 16-bit regions' pass count AND plane traffic)
            "plane_passes_per_topic": (32 * n_chunks32 * chunk32
                                       + 16 * n_chunks16 * chunk16)}


SELECT_EXPAND_MAX = 40   # group count below which the select expansion
                         # beats the one-hot MXU matmul (K = G keeps the
                         # systolic array almost idle at small G)


def _expand_mxu(lo_ref, hi_ref, onehot_ref):
    """[TB, Gp] split signatures -> [TB, C] expanded via one-hot matmul."""
    lo = lo_ref[:]                                      # [TB, Gp] f32
    hi = hi_ref[:]
    # HIGHEST precision: default MXU f32 runs bf16 passes whose 8-bit
    # mantissa would round the 16-bit signature halves
    exp_lo = jnp.dot(lo, onehot_ref[:], precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)  # [TB, C]
    exp_hi = jnp.dot(hi, onehot_ref[:], precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    # Mosaic has no f32->u32 cast; the values are < 2^16 so the i32 hop
    # is exact and the u32 reinterpret free
    exp_lo32 = exp_lo.astype(jnp.int32).astype(jnp.uint32)
    exp_hi32 = exp_hi.astype(jnp.int32).astype(jnp.uint32)
    return (exp_hi32 << 16) | exp_lo32


def _expand_select(sig_ref, grp_ref, n_groups: int):
    """[TB, Gp] signatures -> [TB, C] via per-group masked selects.

    With the '+'-shapes probed on host the device typically holds only a
    handful of '#'-prefix groups, so G compare+selects on the VPU are far
    cheaper than an almost-empty MXU pass ([TB, G] x [G, C] at G ~ 8 uses
    a few percent of the systolic array)."""
    sig = sig_ref[:]                                     # [TB, Gp] u32
    grp = grp_ref[0][None, :]                            # [1, C] int32
    sig_exp = jnp.zeros((sig.shape[0], grp.shape[1]), dtype=jnp.uint32)
    for g in range(n_groups):
        sig_exp = jnp.where(grp == g, sig[:, g][:, None], sig_exp)
    return sig_exp


def _compare_planes32(sig_exp, planes_ref):
    """32 bit-plane passes: bit j of the match word is row 32w+j."""
    acc = jnp.zeros_like(sig_exp)
    for j in range(32):
        acc = acc | ((sig_exp == planes_ref[j][None, :]).astype(jnp.uint32)
                     << jnp.uint32(j))
    return acc


def _compare_planes16(rep, planes_ref):
    """16 packed plane passes: plane j's uint32 carries rows 32w+j (low
    16 bits) and 32w+16+j (high 16 bits); ``rep`` is the topic's folded
    signature replicated into both lanes. The SWAR zero-lane detect
    (x - 1-per-lane) & ~x & lane-sign-bits yields bit 15 for a low-lane
    match and bit 31 for a high-lane match of x = rep ^ plane, so ONE
    pass produces two rows' match bits — half the passes and half the
    plane traffic of the 32-bit loop. Shifting by (15 - j) lands them
    on match-word bits j and 16+j, which is exactly the row layout.

    The detect's one imprecision: a borrow out of a ZERO low lane can
    fake the high-lane bit when hi ^ rep == 1. A fake therefore always
    rides next to the real low-lane bit, making the word multi-bit ->
    collision overflow -> exact CPU fallback."""
    # per-lane constants built inside the trace: a Pallas kernel cannot
    # capture materialized module-level arrays as closure constants
    lane_ones = jnp.uint32(0x00010001)
    lane_high = jnp.uint32(0x80008000)
    acc = jnp.zeros_like(rep)
    for j in range(16):
        x = rep ^ planes_ref[j][None, :]
        zero = (x - lane_ones) & ~x & lane_high
        acc = acc | (zero >> jnp.uint32(15 - j))
    return acc


def _extract_tail(acc, flag_ref, out_ref, max_rows: int, word_base: int):
    """Shared candidate-extraction tail of all chunk kernels."""
    # one word = 32 rows of one group; a real topic matches <=1 row per
    # group, so multi-bit words are hash collisions -> overflow (exact
    # CPU fallback). That makes the bit index one clz op — the garbage
    # value on a multi-bit word never escapes (its topic overflows).
    nz = acc != 0
    multi = (acc & (acc - jnp.uint32(1))) != 0
    counts = nz.astype(jnp.int32).sum(axis=1)            # [TB]
    collided = multi.astype(jnp.int32).sum(axis=1) > 0
    too_deep = flag_ref[:, 0] != 0
    overflow = too_deep | collided | (counts > max_rows)

    bit = jnp.int32(31) - jax.lax.clz(acc.astype(jnp.int32))
    tb, chunk = acc.shape
    wordidx = jax.lax.broadcasted_iota(jnp.int32, (tb, chunk), 1) + word_base
    inf = jnp.int32(0x7FFFFFFF)
    # Mosaic reductions only exist for signed ints: the min-extract runs
    # in int32 (row encodings are < 2^27, INF = INT32_MAX)
    enc = jnp.where(nz, (wordidx << 5) | bit, inf)
    rows = []
    for _ in range(max_rows):
        m = enc.min(axis=1)                              # [TB]
        rows.append(m)
        enc = jnp.where(enc == m[:, None], inf, enc)

    cnt = jnp.where(overflow, jnp.uint32(0xF),
                    jnp.minimum(counts, max_rows).astype(jnp.uint32))
    out = [cnt] + [jnp.where(r == inf, jnp.uint32(0xFFFFFFFF),
                             r.astype(jnp.uint32)) for r in rows]
    out_ref[:] = jnp.stack(out, axis=1)


def _chunk_kernel_mxu(lo_ref, hi_ref, flag_ref, onehot_ref, planes_ref,
                      out_ref, *, max_rows: int, word_base: int,
                      width16: bool):
    """One word-chunk via the one-hot MXU expansion (large group counts).
    A 16-bit chunk expands the replicated fold (equal halves) and runs
    the packed dual-lane compare."""
    sig_exp = _expand_mxu(lo_ref, hi_ref, onehot_ref)
    cmp = _compare_planes16 if width16 else _compare_planes32
    _extract_tail(cmp(sig_exp, planes_ref), flag_ref, out_ref, max_rows,
                  word_base)


def _chunk_kernel_select(sig_ref, flag_ref, grp_ref, planes_ref, out_ref,
                         *, max_rows: int, word_base: int, n_groups: int,
                         width16: bool):
    """One word-chunk via masked-select expansion (small group counts)."""
    sig_exp = _expand_select(sig_ref, grp_ref, n_groups)
    cmp = _compare_planes16 if width16 else _compare_planes32
    _extract_tail(cmp(sig_exp, planes_ref), flag_ref, out_ref, max_rows,
                  word_base)


def _run_chunk_mxu(kern, lo, hi, flag, onehot_c, planes_c, tb, g_pad, chunk,
                   max_rows, plane_rows, interpret):
    nb = lo.shape[0] // tb
    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((tb, g_pad), lambda i: (i, 0)),
            pl.BlockSpec((tb, g_pad), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
            pl.BlockSpec((g_pad, chunk), lambda i: (0, 0)),
            pl.BlockSpec((plane_rows, chunk), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1 + max_rows), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * tb, 1 + max_rows), jnp.uint32),
        interpret=interpret,
    )(lo, hi, flag, onehot_c, planes_c)


def _run_chunk_select(kern, sig, flag, grp_c, planes_c, tb, g_pad, chunk,
                      max_rows, plane_rows, interpret):
    nb = sig.shape[0] // tb
    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((tb, g_pad), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, chunk), lambda i: (0, 0)),
            pl.BlockSpec((plane_rows, chunk), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1 + max_rows), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * tb, 1 + max_rows), jnp.uint32),
        interpret=interpret,
    )(sig, flag, grp_c, planes_c)


def _bake_region_constants(tables, g_pad, chunk, n_chunks, word_lo,
                           n_words_r, width16, select_expand):
    """Per-chunk kernel operands for ONE contiguous single-width word
    region [word_lo, word_lo + n_words_r), padded to its chunk grid.
    Every BlockSpec-visible column must carry the poison scheme (no
    group / zero one-hot => sig_exp 0; plane 0xFFFFFFFF => never equal
    — its 16-bit lanes are the 0xFFFF pad poison no eligible fold
    emits), so grid padding can never produce phantom bits. Padding
    columns' word indices may numerically alias the OTHER region's real
    words, which is safe for the same reason: no bit ever carries
    them."""
    w_full = n_chunks * chunk
    grp_sizes = [int(w) for w in tables.group_words]
    onehot = np.zeros((g_pad, w_full), dtype=np.float32)
    grp_of_word = np.full((1, w_full), -1, dtype=np.int32)
    w0 = 0
    for g, w in enumerate(grp_sizes):
        lo, hi = w0, w0 + w              # global word span of group g
        w0 = hi
        a, b = max(lo, word_lo), min(hi, word_lo + n_words_r)
        if a < b:
            onehot[g, a - word_lo:b - word_lo] = 1.0
            grp_of_word[0, a - word_lo:b - word_lo] = g
    planes_rows = 16 if width16 else 32
    planes = np.full((planes_rows, w_full), 0xFFFFFFFF, dtype=np.uint32)
    # row-backed words only: an empty table still plans one poison word
    # (n_words_r == 1 with no rows behind it) — its planes stay poison
    avail = min(n_words_r, len(tables.row_sig) // 32 - word_lo)
    if avail > 0:
        r0, r1 = 32 * word_lo, 32 * (word_lo + avail)
        if width16:
            s16 = np.asarray(tables.row_sig16[r0:r1],
                             dtype=np.uint32).reshape(avail, 32)
            packed = s16[:, :16] | (s16[:, 16:] << np.uint32(16))
            planes[:, :avail] = packed.T
        else:
            planes[:, :avail] = tables.row_sig[r0:r1].reshape(
                avail, 32).T
    expand_src = grp_of_word if select_expand else onehot
    expand_c = [jax.device_put(jnp.asarray(
        expand_src[:, c * chunk:(c + 1) * chunk]))
        for c in range(n_chunks)]
    planes_c = [jax.device_put(jnp.asarray(
        planes[:, c * chunk:(c + 1) * chunk])) for c in range(n_chunks)]
    return expand_c, planes_c


def _merge_chunk_outputs(outs, max_rows):
    """Fold per-chunk (count | sorted slots) outputs into one sorted row
    set. Merge-by-min-extract: per-chunk slots are already sorted and
    the concat is narrow (NC * max_rows), so max_rows min+mask passes
    beat a full XLA sort."""
    if len(outs) == 1:
        cnt0 = outs[0][:, 0]
        rows_sorted = outs[0][:, 1:]
        overflow = cnt0 == 0xF
        counts = jnp.where(overflow, 0, cnt0).astype(jnp.int32)
        return counts, overflow, rows_sorted
    cnts = jnp.stack([o[:, 0] for o in outs], axis=1)  # [B, NC]
    overflow = (cnts == 0xF).any(axis=1)
    counts = jnp.where(cnts == 0xF, 0,
                       cnts.astype(jnp.int32)).sum(axis=1)
    overflow = overflow | (counts > max_rows)
    cand = jnp.concatenate([o[:, 1:] for o in outs], axis=1)
    merged = []
    for _ in range(max_rows):
        m = cand.min(axis=1)
        merged.append(m)
        cand = jnp.where(cand == m[:, None],
                         jnp.uint32(0xFFFFFFFF), cand)
    return counts, overflow, jnp.stack(merged, axis=1)


def _build_regions(tables: SigTables, kplan: dict, max_rows: int,
                   select_expand: bool) -> list[dict]:
    """Per-region chunk kernels + baked operands: the 32-bit word
    region first, then the packed 16-bit region (matching the
    compile-time group layout). Each region carries its own chunk
    width (capped at the region, see plan) so a small region never
    compares a large region's worth of padding."""
    g_pad = kplan["g_pad"]
    n_groups = len(tables.groups)
    regions = []
    if kplan["n_chunks32"]:
        regions.append({"width16": False, "word_lo": 0,
                        "n_words": kplan["n_words32"],
                        "chunk": kplan["chunk32"],
                        "n_chunks": kplan["n_chunks32"]})
    if kplan["n_chunks16"]:
        regions.append({"width16": True, "word_lo": kplan["n_words32"],
                        "n_words": kplan["n_words16"],
                        "chunk": kplan["chunk16"],
                        "n_chunks": kplan["n_chunks16"]})
    for r in regions:
        r["expand_c"], r["planes_c"] = _bake_region_constants(
            tables, g_pad, r["chunk"], r["n_chunks"], r["word_lo"],
            r["n_words"], r["width16"], select_expand)
        bases = [r["word_lo"] + c * r["chunk"]
                 for c in range(r["n_chunks"])]
        if select_expand:
            r["kerns"] = [functools.partial(
                _chunk_kernel_select, max_rows=max_rows, word_base=b,
                n_groups=n_groups, width16=r["width16"]) for b in bases]
        else:
            r["kerns"] = [functools.partial(
                _chunk_kernel_mxu, max_rows=max_rows, word_base=b,
                width16=r["width16"]) for b in bases]
    return regions


def _run_regions(regions, select_expand, sig_adj, flag, tb, g_pad,
                 max_rows, interpret):
    """Dispatch every region's chunk kernels for one traced batch
    (each chunk compares against its own width's plane slice, at its
    region's chunk width)."""
    outs = []
    if select_expand:
        for r in regions:
            outs += [_run_chunk_select(
                r["kerns"][c], sig_adj, flag, r["expand_c"][c],
                r["planes_c"][c], tb, g_pad, r["chunk"], max_rows,
                16 if r["width16"] else 32, interpret)
                for c in range(r["n_chunks"])]
        return outs
    lo = (sig_adj & jnp.uint32(0xFFFF)).astype(jnp.float32)
    hi = (sig_adj >> jnp.uint32(16)).astype(jnp.float32)
    for r in regions:
        outs += [_run_chunk_mxu(
            r["kerns"][c], lo, hi, flag, r["expand_c"][c],
            r["planes_c"][c], tb, g_pad, r["chunk"], max_rows,
            16 if r["width16"] else 32, interpret)
            for c in range(r["n_chunks"])]
    return outs


def build_fixed_fn(tables: SigTables, consts: dict, kplan: dict,
                   max_rows: int):
    """(jit(toks8, lens_enc) -> (counts_u8, row stream), format
    descriptor) via the fused chunk kernels + XLA merge — one device
    dispatch per batch.

    ``consts`` are the engine's device constants (for the [B, G] signature
    prologue, which stays in XLA — it is tiny). The expansion one-hot and
    bit-plane tables are sliced per chunk and baked as kernel operands,
    region by region (``_build_regions``). The 16-bit groups' topic
    signatures are folded and lane-replicated in the XLA prologue
    ([B, G] work — noise next to the [B, W] compare), so the expansion
    machinery is width-agnostic. The wire format is "stream": one uint8
    count per topic plus the matched row ids compacted in topic order
    (see the compaction step below); sig.py's unpack switches on the
    descriptor."""
    g_pad, tb = kplan["g_pad"], kplan["tb"]
    select_expand = len(tables.groups) <= SELECT_EXPAND_MAX
    regions = _build_regions(tables, kplan, max_rows, select_expand)

    # row encodings are (word << 5) | bit < bound * 32; bit_length of
    # the EXCLUSIVE bound keeps the all-ones sentinel unreachable even
    # when the bound is a power of two
    enc_bound = max(32 * (r["word_lo"] + r["n_chunks"] * r["chunk"])
                    for r in regions)
    enc_bits = enc_bound.bit_length()

    # the CPU backend runs the kernel in the Pallas interpreter; it is
    # only ever the backend on request (accel.require_accelerator)
    interpret = jax.default_backend() != "tpu"
    has16 = bool(kplan["n_chunks16"])
    if has16:
        fold_dev = jnp.asarray(np.asarray(tables.fold_mult,
                                          dtype=np.uint32))
        w16_dev = jnp.asarray(width16_mask(tables))

    @jax.jit
    def fn(toks8, lens_enc):
        batch = toks8.shape[0]
        dollar = lens_enc < 0
        lengths = jnp.abs(lens_enc.astype(jnp.int32))
        sig_adj = adjusted_signatures(consts, toks8.astype(jnp.int32),
                                      lengths, dollar)      # [B, G]
        if has16:
            # fold the 16-bit groups' signatures and replicate them into
            # both uint32 lanes for the packed compare; 32-bit groups
            # keep the raw signature. Poisoned (invalid-group) sigs fold
            # to a value that collides with a row only at the 2^-16
            # baseline — host verification absorbs it like any collision
            folded = (sig_adj * fold_dev[None, :]) >> jnp.uint32(16)
            sig_adj = jnp.where(w16_dev[None, :],
                                folded | (folded << jnp.uint32(16)),
                                sig_adj)
        pad_g = g_pad - sig_adj.shape[1]
        if pad_g:
            sig_adj = jnp.pad(sig_adj, ((0, 0), (0, pad_g)))
        flag = (lengths >= 127).astype(jnp.int32)[:, None]

        pad_b = (-batch) % tb
        if pad_b:
            sig_adj = jnp.pad(sig_adj, ((0, pad_b), (0, 0)))
            flag = jnp.pad(flag, ((0, pad_b), (0, 0)))

        outs = _run_regions(regions, select_expand, sig_adj, flag, tb,
                            g_pad, max_rows, interpret)
        counts, overflow, rows_sorted = _merge_chunk_outputs(outs,
                                                             max_rows)

        # stream compaction: the fetch crosses the host link, so the
        # wire format is ONE uint8 count per topic plus the matched row
        # ids concatenated in topic order — ~1 + 4*matches bytes/topic
        # instead of max_rows mostly-empty fixed slots. The host fetches
        # the counts, sums them, and fetches only the used front of the
        # stream.
        counts_real = jnp.where(overflow, 0, counts)
        counts_u8 = jnp.where(
            overflow, jnp.uint32(0xFF),
            jnp.minimum(counts, max_rows).astype(jnp.uint32)
        ).astype(jnp.uint8)
        offs = jnp.cumsum(counts_real) - counts_real        # exclusive
        kidx = jnp.arange(max_rows, dtype=jnp.int32)[None, :]
        valid = kidx < counts_real[:, None]
        cap = rows_sorted.shape[0] * max_rows
        pos = jnp.where(valid, offs[:, None] + kidx, cap)
        stream = jnp.zeros((cap,), jnp.uint32).at[
            pos.reshape(-1)].set(rows_sorted.reshape(-1), mode="drop")
        return counts_u8[:batch], stream

    @functools.wraps(fn)         # .__wrapped__: the jitted program itself
    def fn_surfaced(toks8, lens_enc):
        # kernel-launch / runtime failures come back as opaque XLA
        # exceptions; re-raise typed so the ADR-011 supervisor's logs
        # separate a sick device from a host bug (the supervisor answers
        # from the CPU trie either way)
        try:
            return fn(toks8, lens_enc)
        except Exception as exc:
            from ..faults import DeviceMatchError
            raise DeviceMatchError(
                f"fused sig kernel dispatch failed: {exc!r:.300}") from exc

    return fn_surfaced, {"kind": "stream", "enc_bits": enc_bits,
                         "max_rows": max_rows}
