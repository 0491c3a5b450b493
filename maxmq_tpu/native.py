"""ctypes bindings for the C++ native host runtime (native/maxmq_native.cpp).

Loads ``libmaxmq_native.so`` (building it with ``make -C native`` on first
use if a compiler is available), and exposes:

* ``NativeVocab`` / ``tokenize`` — the batch topic tokenizer feeding the TPU
  matchers; exact drop-in for matching/topics.py:tokenize_topics.
* ``scan_frames`` — the MQTT fixed-header frame scanner; slices a byte
  buffer of concatenated control packets into frames without per-byte
  Python work (same framing rules as protocol/codec.py).

Everything degrades to the pure-Python paths: ``available()`` is False when
the library can't be built/loaded (logged once, with the error) or
MAXMQ_NO_NATIVE is set. The build runs only when the ``.so`` is absent, so
concurrent processes never race on one file; ``make -C native`` rebuilds a
stale one (chip_smoke.py runs it first).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.environ.get("MAXMQ_NATIVE_DIR") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libmaxmq_native.so")

_log = logging.getLogger("maxmq.native")
_lib = None
_load_lock = threading.Lock()
_load_attempted = False


def _build(target: str | None = None) -> bool:
    """``make -C native [target]``; a failure is logged with the
    compiler's own words (each library is attempted once per process,
    so once) and the caller serves from the Python path."""
    cmd = ["make", "-C", _NATIVE_DIR, "-s"] + ([target] if target else [])
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        _log.error("native build failed (%s): %r %s", " ".join(cmd), exc,
                   detail.decode("utf-8", "replace")[-2000:])
        return False
    return True


def _try_load():
    global _lib, _load_attempted
    with _load_lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        if os.environ.get("MAXMQ_NO_NATIVE"):
            return None
        # on-demand build only where a Makefile exists — an override dir
        # (MAXMQ_NATIVE_DIR, e.g. native/asan) holds prebuilt .so only
        if (not os.path.exists(_SO_PATH)
                and os.path.exists(os.path.join(_NATIVE_DIR, "Makefile"))
                and not _build()):
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as exc:
            _log.error("native library %s did not load: %r", _SO_PATH, exc)
            return None
        lib.mq_vocab_new.restype = ctypes.c_void_p
        lib.mq_vocab_free.argtypes = [ctypes.c_void_p]
        lib.mq_vocab_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int64, ctypes.c_int32]
        lib.mq_vocab_size.argtypes = [ctypes.c_void_p]
        lib.mq_vocab_size.restype = ctypes.c_int64
        lib.mq_tokenize.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            np.ctypeslib.ndpointer(np.int64), ctypes.c_int64,
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32),
            np.ctypeslib.ndpointer(np.int32),
            np.ctypeslib.ndpointer(np.uint8)]
        lib.mq_tokenize_joined.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32),
            np.ctypeslib.ndpointer(np.int32),
            np.ctypeslib.ndpointer(np.uint8)]
        lib.mq_scan_frames.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.int64),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)]
        lib.mq_scan_frames.restype = ctypes.c_int64
        lib.mq_tokenize_sig.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            np.ctypeslib.ndpointer(np.uint32),
            np.ctypeslib.ndpointer(np.uint32),
            np.ctypeslib.ndpointer(np.uint8), ctypes.c_int64,
            ctypes.c_void_p,
            np.ctypeslib.ndpointer(np.int8),
            np.ctypeslib.ndpointer(np.uint32)]
        lib.mq_probe_new.restype = ctypes.c_void_p
        lib.mq_probe_free.argtypes = [ctypes.c_void_p]
        lib.mq_probe_add_group.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_uint8,
            ctypes.c_uint32,
            np.ctypeslib.ndpointer(np.uint32),
            np.ctypeslib.ndpointer(np.uint32),
            np.ctypeslib.ndpointer(np.int32), ctypes.c_int64]
        lib.mq_probe_run.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
            np.ctypeslib.ndpointer(np.int8), ctypes.c_int64,
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.int32), ctypes.c_int64,
            ctypes.c_int32]
        lib.mq_probe_run.restype = ctypes.c_int64
        lib.mq_probe_set_ge.argtypes = [ctypes.c_void_p]
        lib.mq_tokenize_probe.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p,
            np.ctypeslib.ndpointer(np.int8),
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.int32), ctypes.c_int64]
        lib.mq_tokenize_probe.restype = ctypes.c_int64
        _lib = lib
        return _lib


def available() -> bool:
    return _try_load() is not None


# CPython extensions by name: the module, or None once a build or load
# failed (logged once); absent from the dict while still retriable
_extensions: dict = {}


def chain_params_in_effect(mod) -> tuple:
    """The decode extension's live (min_base, tail_num, tail_den) — the
    value A/B harnesses and test finally blocks must restore VERBATIM
    (restoring hardcoded defaults silently changes global decode
    behavior if the native defaults drift). Falls back to the
    historical defaults only when the loaded extension predates the
    ``_get_chain_params`` getter."""
    getter = getattr(mod, "_get_chain_params", None)
    return getter() if getter is not None else (64, 1, 1)


def _extension(name: str, build: bool):
    """The CPython extension ``native/<name>.so``, or None. Built on
    first use where a Makefile is (``build``), loaded by path."""
    with _load_lock:
        if name in _extensions:
            return _extensions[name]
        if os.environ.get("MAXMQ_NO_NATIVE"):
            _extensions[name] = None
            return None
        path = os.path.join(_NATIVE_DIR, f"{name}.so")
        if not os.path.exists(path):
            if (not build or not os.path.exists(
                    os.path.join(_NATIVE_DIR, "Makefile"))):
                return None            # stay retriable for build=True
            if not _build(f"{name}.so"):
                _extensions[name] = None
                return None
        try:
            import importlib.util
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except Exception as exc:
            _log.error("native extension %s did not load: %r", path, exc)
            mod = None
        _extensions[name] = mod
        return mod


def decode_module(build: bool = True):
    """The maxmq_decode CPython extension (candidate verify + subscriber
    union in C; see native/maxmq_decode.cpp), or None. A separate .so
    from the ctypes runtime because its hot loop builds Python objects —
    that needs the C API, not a C ABI.

    ``build=False`` only loads an already-built .so (import-time callers
    must not block on a compile); the device match path passes the
    default and compiles on demand."""
    return _extension("maxmq_decode", build)


def sender_module():
    """The maxmq_sender CPython extension (the flush pass's socket
    writer thread; see native/maxmq_sender.cpp), or None."""
    return _extension("maxmq_sender", True)


class NativeVocab:
    """C++ mirror of a matcher vocabulary dict (level string -> token id).
    Built once per table refresh; reads are lock-free in C++."""

    def __init__(self, vocab: dict[str, int]) -> None:
        lib = _try_load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = ctypes.c_void_p(lib.mq_vocab_new())
        for level, tok in vocab.items():
            raw = level.encode("utf-8")
            lib.mq_vocab_add(self._handle, raw, len(raw), tok)

    def __len__(self) -> int:
        return int(self._lib.mq_vocab_size(self._handle))

    def __del__(self):
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and getattr(self, "_lib", None) is not None:
            self._lib.mq_vocab_free(handle)

    def tokenize(self, topics: list[str], max_levels: int):
        """Same contract as matching/topics.py:tokenize_topics. Topics are
        shipped as ONE NUL-joined utf-8 buffer (U+0000 can't appear in an
        MQTT topic name [MQTT-1.5.4-2]) and split in C."""
        n = len(topics)
        buf = "\x00".join(topics).encode("utf-8")
        toks = np.empty((n, max_levels), dtype=np.int32)
        lengths = np.empty(n, dtype=np.int32)
        dollar = np.empty(n, dtype=np.uint8)
        self._lib.mq_tokenize_joined(self._handle, buf, len(buf), n,
                                     max_levels, toks, lengths, dollar)
        return toks, lengths, dollar.astype(bool)


class ExactSigTable:
    """Host-exact coefficient tables marshalled once per compiled-table
    snapshot for mq_tokenize_sig (depth -> per-position multipliers)."""

    def __init__(self, host_exact: dict) -> None:
        max_d = max(host_exact.keys(), default=0)
        self.max_d = max_d
        self.coef = np.zeros((max_d + 1, max(max_d, 1)), dtype=np.uint32)
        self.dc = np.zeros(max_d + 1, dtype=np.uint32)
        self.present = np.zeros(max_d + 1, dtype=np.uint8)
        for d, g in host_exact.items():
            spec = g.spec
            for c, pos in zip(spec.coef, spec.kept):
                self.coef[d, pos] = c
            self.dc[d] = spec.depth_coef
            self.present[d] = 1


def tokenize_sig(vocab: "NativeVocab", topics: list[str], window: int,
                 tok_dtype, exact: ExactSigTable):
    """One-pass compact tokenizer + host-exact signature (C++). Returns
    (toks [n, window] of tok_dtype, lens_enc int8[n], esig uint32[n]) per
    maxmq_tpu/matching/sig.py:tokenize_compact's encoding contract."""
    lib = vocab._lib
    n = len(topics)
    buf = "\x00".join(topics).encode("utf-8")
    toks = np.empty((n, window), dtype=tok_dtype)
    lens = np.empty(n, dtype=np.int8)
    esig = np.empty(n, dtype=np.uint32)
    mode = {np.uint8: 1, np.uint16: 2, np.int32: 4}[tok_dtype]
    lib.mq_tokenize_sig(vocab._handle, buf, len(buf), n, window, mode,
                        exact.coef, exact.dc, exact.present,
                        exact.coef.shape[1] if exact.max_d else 0,
                        toks.ctypes.data_as(ctypes.c_void_p), lens, esig)
    return toks, lens, esig


class NativeProbe:
    """C++ host probe over every exact-shape group (full-literal +
    '+'-shape): one hashed signature + binary search per (topic, group
    of the topic's depth), threaded over topic ranges. Built once per
    compiled-table snapshot from tables.host_exact / tables.host_plus."""

    def __init__(self, host_exact: dict, host_plus: dict,
                 ge_depth: bool = False) -> None:
        lib = _try_load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = ctypes.c_void_p(lib.mq_probe_new())
        for d, g in (host_exact or {}).items():
            coef = np.zeros(max(d, 1), dtype=np.uint32)
            for c, pos in zip(g.spec.coef, g.spec.kept):
                coef[pos] = c
            with np.errstate(over="ignore"):
                dc = int(np.uint32(g.spec.depth_coef) * np.uint32(d))
            lib.mq_probe_add_group(
                self._handle, d, 0, dc, coef,
                np.ascontiguousarray(g.sigs, dtype=np.uint32),
                np.ascontiguousarray(g.rows, dtype=np.int32), len(g.sigs))
        for d, p in (host_plus or {}).items():
            for k in range(len(p.sigs)):
                lib.mq_probe_add_group(
                    self._handle, d, int(bool(p.wildf[k])), int(p.dc[k]),
                    np.ascontiguousarray(p.coef[k], dtype=np.uint32),
                    np.ascontiguousarray(p.sigs[k], dtype=np.uint32),
                    np.ascontiguousarray(p.rows[k], dtype=np.int32),
                    len(p.sigs[k]))
        if ge_depth:
            # '#'-prefix semantics: groups apply to topics of depth >=
            # their prefix depth (pass tables.host_hash as host_plus —
            # same probe layout, dc=0). Must follow every add_group.
            lib.mq_probe_set_ge(self._handle)

    def __del__(self):
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and getattr(self, "_lib", None) is not None:
            self._lib.mq_probe_free(handle)

    def run(self, toks: np.ndarray, lens_enc: np.ndarray
            ) -> tuple[np.ndarray, np.ndarray]:
        """(topic ids int64[M], row ids int32[M]) hit pairs, topic-sorted.
        ``toks`` is the narrow [n, window] token matrix of any of the
        compact dtypes."""
        n, window = toks.shape
        mode = {1: 1, 2: 2, 4: 4}[toks.dtype.itemsize]
        cap = max(4 * n, 1024)
        while True:
            ti = np.empty(cap, dtype=np.int64)
            rw = np.empty(cap, dtype=np.int32)
            total = self._lib.mq_probe_run(
                self._handle, toks.ctypes.data_as(ctypes.c_void_p), mode,
                lens_enc, n, window, ti, rw, cap, 0)
            if total <= cap:
                return ti[:total], rw[:total]
            cap = int(total)


def tokenize_probe(vocab: "NativeVocab", probe: "NativeProbe",
                   topics: list[str], window: int, tok_dtype):
    """Fused single-pass tokenize + host probe (C++): returns
    (toks [n, window] of tok_dtype, lens_enc int8[n], ti int64[M],
    rows int32[M]) — hit pairs topic-sorted. One pass over the topic
    bytes with the level tokens still in registers at probe time."""
    lib = vocab._lib
    n = len(topics)
    buf = "\x00".join(topics).encode("utf-8")
    toks = np.empty((n, window), dtype=tok_dtype)
    lens = np.empty(n, dtype=np.int8)
    mode = {np.uint8: 1, np.uint16: 2, np.int32: 4}[tok_dtype]
    cap = max(4 * n, 1024)
    while True:
        ti = np.empty(cap, dtype=np.int64)
        rw = np.empty(cap, dtype=np.int32)
        total = lib.mq_tokenize_probe(
            vocab._handle, probe._handle, buf, len(buf), n, window, mode,
            toks.ctypes.data_as(ctypes.c_void_p), lens, ti, rw, cap)
        if total <= cap:
            return toks, lens, ti[:total], rw[:total]
        cap = int(total)


class MalformedFrame(ValueError):
    """The buffer contains an invalid fixed header (reserved type 0 or a
    variable-byte integer longer than 4 bytes, MQTT-1.5.5)."""


def scan_frames(data: bytes, max_frames: int = 4096
                ) -> tuple[list[tuple[int, int]], int]:
    """Scan ``data`` for complete MQTT frames.

    Returns ``(frames, consumed)`` where frames is a list of (start, end)
    byte ranges and consumed is the offset scanning stopped at (start of the
    first incomplete frame — the caller keeps ``data[consumed:]`` for the
    next read). Raises MalformedFrame on an invalid header.
    """
    lib = _try_load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    starts = np.empty(max_frames, dtype=np.int64)
    totals = np.empty(max_frames, dtype=np.int64)
    consumed = ctypes.c_int64(0)
    n = lib.mq_scan_frames(data, len(data), starts, totals, max_frames,
                           ctypes.byref(consumed))
    if n < 0:
        raise MalformedFrame(f"invalid fixed header at offset {consumed.value}")
    return ([(int(starts[i]), int(starts[i] + totals[i])) for i in range(n)],
            int(consumed.value))


def scan_frames_py(data: bytes, max_frames: int = 4096
                   ) -> tuple[list[tuple[int, int]], int]:
    """Pure-Python reference for scan_frames (also the fallback)."""
    frames: list[tuple[int, int]] = []
    pos = 0
    while pos < len(data) and len(frames) < max_frames:
        if (data[pos] >> 4) == 0:
            raise MalformedFrame(f"invalid fixed header at offset {pos}")
        rem = 0
        shift = 0
        vpos = pos + 1
        complete = False
        while vpos < len(data):
            b = data[vpos]
            vpos += 1
            rem |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                complete = True
                break
            if shift > 21:
                raise MalformedFrame(
                    f"invalid fixed header at offset {pos}")
        if not complete:
            break
        total = (vpos - pos) + rem
        if pos + total > len(data):
            break
        frames.append((pos, pos + total))
        pos += total
    return frames, pos
