"""Tests for the C++ native host runtime: tokenizer parity with the Python
path and frame-scanner parity with both the Python reference scanner and the
real packet codec."""

from __future__ import annotations

import random

import numpy as np
import pytest

from maxmq_tpu import native
from maxmq_tpu.matching.topics import tokenize_topics
from maxmq_tpu.protocol.codec import FixedHeader, PacketType
from maxmq_tpu.protocol.packets import Packet, Subscription

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library not built")


def rand_topics(rng: random.Random, n: int) -> list[str]:
    segs = ["sensor", "data", "", "Ω-unit", "dev1", "$SYS", "a" * 60, "+",
            "#", "x"]
    out = []
    for _ in range(n):
        depth = rng.randint(1, 12)
        out.append("/".join(rng.choice(segs) for _ in range(depth)))
    out += ["", "/", "//", "$", "$SYS/broker/load", "no-slash"]
    return out


class TestTokenizer:
    def test_parity_with_python(self):
        rng = random.Random(5)
        vocab = {}
        for i, level in enumerate(["sensor", "data", "dev1", "$SYS", "",
                                   "Ω-unit", "x"]):
            vocab[level] = i + 1
        nv = native.NativeVocab(vocab)
        assert len(nv) == len(vocab)
        topics = rand_topics(rng, 500)
        for max_levels in (1, 4, 16):
            t1, l1, d1 = tokenize_topics(vocab, topics, max_levels)
            t2, l2, d2 = nv.tokenize(topics, max_levels)
            assert np.array_equal(l1, l2)
            assert np.array_equal(d1, d2)
            assert np.array_equal(t1, t2)

    def test_unknown_levels_get_unk(self):
        nv = native.NativeVocab({"a": 1})
        toks, lengths, dollar = nv.tokenize(["a/zzz/a"], 4)
        assert toks.tolist() == [[1, 0, 1, -1]]
        assert lengths.tolist() == [3]
        assert not dollar[0]

    def test_overflow_marks_minus_one(self):
        nv = native.NativeVocab({})
        toks, lengths, _ = nv.tokenize(["a/b/c/d/e"], 3)
        assert lengths.tolist() == [-1]
        assert (toks == -1).all()

    def test_engine_uses_native_tokenizer(self):
        from maxmq_tpu.matching import TopicIndex
        from maxmq_tpu.matching.sig import SigEngine
        idx = TopicIndex()
        idx.subscribe("c1", Subscription(filter="a/+"))
        engine = SigEngine(idx)
        engine.route_small = False      # not the ADR-008 trie router
        (got,) = engine.subscribers_host_batch(["a/b"])
        assert sorted(got.subscriptions) == ["c1"]
        assert engine.tables.__dict__.get("_native_vocab") is not None


def encode(ptype: int, payload: bytes = b"") -> bytes:
    out = bytearray([ptype << 4])
    rem = len(payload)
    while True:
        b = rem % 128
        rem //= 128
        out.append(b | (0x80 if rem else 0))
        if not rem:
            break
    return bytes(out) + payload


class TestFrameScanner:
    def test_complete_frames(self):
        data = (encode(PacketType.PINGREQ) +
                encode(PacketType.PUBLISH, b"x" * 300) +
                encode(PacketType.DISCONNECT))
        frames, consumed = native.scan_frames(data)
        assert consumed == len(data)
        assert [data[s] >> 4 for s, _ in frames] == [
            PacketType.PINGREQ, PacketType.PUBLISH, PacketType.DISCONNECT]
        assert frames == native.scan_frames_py(data)[0]

    def test_partial_tail_frame(self):
        full = encode(PacketType.PUBLISH, b"y" * 50)
        data = encode(PacketType.PINGREQ) + full[:20]
        frames, consumed = native.scan_frames(data)
        assert len(frames) == 1
        assert consumed == 2  # scanning stopped at the truncated PUBLISH
        assert native.scan_frames_py(data) == (frames, consumed)

    def test_truncated_varint_waits(self):
        data = bytes([PacketType.PUBLISH << 4, 0x80, 0x80])
        frames, consumed = native.scan_frames(data)
        assert frames == [] and consumed == 0

    def test_malformed_type_zero(self):
        with pytest.raises(native.MalformedFrame):
            native.scan_frames(b"\x00\x00")
        with pytest.raises(native.MalformedFrame):
            native.scan_frames_py(b"\x00\x00")

    def test_malformed_overlong_varint(self):
        data = bytes([PacketType.PUBLISH << 4, 0x80, 0x80, 0x80, 0x80, 0x80])
        with pytest.raises(native.MalformedFrame):
            native.scan_frames(data)
        with pytest.raises(native.MalformedFrame):
            native.scan_frames_py(data)

    def test_parity_against_real_codec_stream(self):
        """Scan a stream of real encoded packets; boundaries must slice
        each packet exactly."""
        packets = []
        p = Packet(fixed=FixedHeader(type=PacketType.PUBLISH, qos=1))
        p.topic, p.packet_id, p.payload = "a/b", 7, b"hello"
        packets.append(p.encode())
        s = Packet(fixed=FixedHeader(type=PacketType.SUBSCRIBE),
                   protocol_version=5)
        s.packet_id = 9
        s.filters = [Subscription(filter="x/#", qos=1)]
        packets.append(s.encode())
        packets.append(Packet(
            fixed=FixedHeader(type=PacketType.PINGRESP)).encode())
        data = b"".join(packets)
        frames, consumed = native.scan_frames(data)
        assert consumed == len(data)
        assert [data[a:b] for a, b in frames] == packets

    def test_random_fuzz_parity(self):
        rng = random.Random(11)
        for _ in range(50):
            data = bytes(rng.randrange(256)
                         for _ in range(rng.randint(0, 200)))
            try:
                got = native.scan_frames(data)
            except native.MalformedFrame:
                with pytest.raises(native.MalformedFrame):
                    native.scan_frames_py(data)
                continue
            assert got == native.scan_frames_py(data)


def test_tokenize_sig_parity_with_python():
    """mq_tokenize_sig must produce exactly tokenize_compact's encoding and
    the same host-exact hits as the numpy path."""
    import numpy as np
    import pytest

    from maxmq_tpu import native
    from maxmq_tpu.matching import TopicIndex
    from maxmq_tpu.matching.sig import (compile_sig, host_exact_rows,
                                        prepare_batch, tokenize_compact)
    from maxmq_tpu.protocol import Subscription

    if not native.available():
        pytest.skip("native library unavailable")

    idx = TopicIndex()
    idx.subscribe("c1", Subscription(filter="a/b/c"))
    idx.subscribe("c2", Subscription(filter="a/b"))
    idx.subscribe("c3", Subscription(filter="x/+/z"))
    idx.subscribe("c4", Subscription(filter="deep/#"))
    tables = compile_sig(idx)
    topics = ["a/b/c", "a/b", "x/q/z", "$SYS/x", "unknown/levels/here",
              "a//b", "", "deep", "t/" + "/".join(["v"] * 80)]

    toks_py, lens_py, toks32, lengths = tokenize_compact(tables, topics)
    hr_py = host_exact_rows(tables, toks32, lengths)
    from maxmq_tpu.matching.sig import host_plus_rows
    host_plus_rows(tables, toks_py, lengths, lens_py < 0, into=hr_py)

    toks_n, lens_n, hr_n = prepare_batch(tables, topics)
    assert toks_n.dtype == toks_py.dtype
    assert np.array_equal(toks_n, toks_py)
    assert np.array_equal(lens_n, lens_py)
    for a, b in zip(hr_n, hr_py):
        assert np.array_equal(a, b)


def _decode_mod():
    from maxmq_tpu.native import decode_module
    mod = decode_module()
    if mod is None:
        pytest.skip("maxmq_decode extension unavailable")
    return mod


def test_get_chain_params_round_trip():
    """_get_chain_params reports the live values so finally blocks can
    restore exactly what was in effect (ADVICE r5 #3)."""
    mod = _decode_mod()
    if not hasattr(mod, "_get_chain_params"):
        pytest.skip("getter unavailable (stale extension)")
    saved = mod._get_chain_params()
    try:
        mod._set_chain_params(17, 3, 2)
        assert mod._get_chain_params() == (17, 3, 2)
    finally:
        mod._set_chain_params(*saved)
    assert mod._get_chain_params() == saved


def test_prewarm_bases_continues_past_oversized_rows():
    """One row too fat for the 3/4 slot-map budget must not abort the
    whole prewarm sweep (ADVICE r5 #4): smaller later rows still get
    their anchors. Exercised at test scale by shrinking the budget so
    the FIRST fat row exceeds it while a later, thinner fat row fits."""
    from maxmq_tpu.matching import TopicIndex
    from maxmq_tpu.matching.sig import _native_decode, compile_sig

    mod = _decode_mod()
    for attr in ("_set_slot_map_cap", "_get_slot_map_cap",
                 "_slot_map_stats", "prewarm_bases"):
        if not hasattr(mod, attr):
            pytest.skip(f"{attr} unavailable (stale extension)")

    idx = TopicIndex()
    # row order follows subscription order: the 40-entry row first
    for i in range(40):
        idx.subscribe(f"big{i}", Subscription(filter="pb/big/#", qos=1))
    for i in range(20):
        idx.subscribe(f"small{i}", Subscription(filter="pb/small/#",
                                                qos=1))
    tables = compile_sig(idx)
    nd = _native_decode(tables)
    assert nd is not None
    _mod, cap = nd
    from maxmq_tpu.native import chain_params_in_effect
    saved_chain = chain_params_in_effect(mod)
    saved_cap = mod._get_slot_map_cap()
    try:
        mod._set_chain_params(16, 1, 1)     # both rows anchor-eligible
        # budget 48: 3/4 bar = 36 — the 40-entry row exceeds it, the
        # 20-entry row fits; the old code ended the sweep at the fat row
        mod._set_slot_map_cap(48)
        r = mod.prewarm_bases(cap, 0, 1000)
        rows_mapped, entries = mod._slot_map_stats(cap)
        assert r == len(tables.row_entries), r
        assert rows_mapped == 1, (rows_mapped, entries)
        assert entries == 20, entries
    finally:
        mod._set_slot_map_cap(saved_cap)
        mod._set_chain_params(*saved_chain)
