"""The served device programs, compiled for a TPU v5e that is described and
not attached (guide ``on-chip-measurement`` section 2): what the chip's
compiler would refuse, it refuses here, at no chip time. A compile that
passes is not a chip run; ``chip_smoke.py`` is.

The topology is described inside a fixture, in this file only, and every
compile runs in the test's own process: the process that describes it
holds the TPU library until it exits.
"""

import random

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from maxmq_tpu.matching import sig, sig_pallas
from maxmq_tpu.matching.trie import TopicIndex
from maxmq_tpu.protocol.packets import Subscription


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def corpus_index(n: int, seed: int = 42) -> TopicIndex:
    """The fleet corpus's ``+``/``#`` mix with 10% ``$share`` (the
    shape chip_smoke.py and the benchmark's ``fleet-1m`` serve at 1M),
    at ``n`` filters."""
    rng = random.Random(seed)
    alphabet = [f"{c}{i}" for c in "abcdefgh" for i in range(12)]
    index = TopicIndex()
    for i in range(n):
        depth = rng.randint(3, 8)
        levels = [rng.choice(alphabet) for _ in range(depth)]
        r = rng.random()
        if r < 0.3:
            for _ in range(rng.randint(1, 2)):
                levels[rng.randrange(depth)] = "+"
        elif r < 0.45:
            levels = levels[: rng.randint(1, depth)] + ["#"]
        f = "/".join(levels)
        if rng.random() < 0.1:
            f = f"$share/g{rng.randint(0, 7)}/{f}"
        index.subscribe(f"cl-{i}", Subscription(filter=f, qos=i % 3))
    return index


def fused_program(tables, monkeypatch):
    """(jitted fixed program, plan) built as on a TPU: the kernel picks
    interpret mode from jax.default_backend(), which sees the CPU here,
    so the test answers for it while the program is built."""
    consts = {k: jax.numpy.asarray(getattr(tables, k)) for k in
              ("topo_coef", "depth_coef", "min_depth", "is_hash",
               "wild_first")}
    kplan = sig_pallas.plan(tables)
    assert kplan is not None
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        fn, fmt = sig_pallas.build_fixed_fn(tables, consts, kplan,
                                            max_rows=7)
    assert fmt["kind"] == "stream"
    return fn.__wrapped__, kplan


def compile_fused(program, tables, bucket: int, sharding):
    toks8, lens_enc, _ = sig.prepare_batch(tables, ["a0/b1/c2"])
    toks = jax.ShapeDtypeStruct((bucket,) + toks8.shape[1:], toks8.dtype,
                                sharding=sharding)
    lens = jax.ShapeDtypeStruct((bucket,), lens_enc.dtype,
                                sharding=sharding)
    return program.lower(toks, lens).compile()


@pytest.fixture(scope="module")
def tables_100k():
    return sig.compile_sig(corpus_index(100_000), max_levels=16)


@pytest.mark.parametrize("bucket", [16, 256])
def test_fused_program_compiles_at_served_buckets(
        tables_100k, one_chip, monkeypatch, bucket):
    program, kplan = fused_program(tables_100k, monkeypatch)
    compiled = compile_fused(program, tables_100k, bucket, one_chip)
    # one Mosaic kernel per word chunk, none swapped for an XLA body
    assert compiled.as_text().count("tpu_custom_call") >= kplan["n_chunks"]


def test_fused_program_compiles_at_full_chunk_width_both_regions(
        one_chip, monkeypatch):
    """CHUNK_WORDS-wide chunks in the 32-bit AND the packed-16 region:
    the widest tiles any corpus can reach (VMEM use does not grow past
    them). Tables fabricated as tests/test_sig_parity.py's plan-bound
    cases are: a real two-width compile, widened."""
    monkeypatch.setattr(sig, "W16_MAX_GROUP_ROWS", 8)
    index = TopicIndex()
    for i in range(30):
        index.subscribe(f"w{i}", Subscription(filter=f"k{i}/#", qos=1))
    for i in range(5):
        index.subscribe(f"n{i}", Subscription(filter=f"m/z{i}/#", qos=2))
    tables = sig.compile_sig(index)
    assert list(tables.group_w16) == [False, True]
    words = sig_pallas.CHUNK_WORDS
    rng = np.random.default_rng(0)
    tables.group_words = np.asarray([2 * words, words], dtype=np.int32)
    tables.row_sig = rng.integers(0, 1 << 32, 3 * words * 32,
                                  dtype=np.uint32)
    tables.row_sig16 = rng.integers(0, 0xFFFF, 3 * words * 32,
                                    dtype=np.uint16)
    tables.n_rows = 3 * words * 32
    program, kplan = fused_program(tables, monkeypatch)
    assert kplan["chunk32"] == kplan["chunk16"] == words
    assert (kplan["n_chunks32"], kplan["n_chunks16"]) == (2, 1)
    compiled = compile_fused(program, tables, 256, one_chip)
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_sharded_sig_step_compiles_on_1x4_mesh(topo):
    """matcher_mesh = "1x4": the cluster-mode step partitioned over four
    described chips, tables on 'subs'. Its shards never talk to each
    other, so the compiled module must hold no collective."""
    from maxmq_tpu.parallel import sharded

    index = corpus_index(20_000)
    shards = sharded.compile_sig_shards(index.all_subscriptions(), 4, 1)
    stacked, d_max = sharded._pad_and_stack_shards(shards, 4)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                axis_names=("data", "subs"))
    program = sharded.sharded_sig_program(mesh, ("subs",), sel_blocks=8,
                                          max_rows=7)
    by_shard = NamedSharding(mesh, P(("subs",)))
    by_batch = NamedSharding(mesh, P("data"))
    toks, lens_enc, _esig, _lengths = sig.prepare_batch_sig(
        shards[0], ["a0/b1/c2"], window=max(d_max, 1), host_exact={})
    args = (tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=by_shard)
                  for a in stacked),
            jax.ShapeDtypeStruct((256,) + toks.shape[1:], toks.dtype,
                                 sharding=by_batch),
            jax.ShapeDtypeStruct((256,), lens_enc.dtype,
                                 sharding=by_batch))
    compiled = program.lower(*args).compile()
    text = compiled.as_text()
    assert "num_partitions=4" in text
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
