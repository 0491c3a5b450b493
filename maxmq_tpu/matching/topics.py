"""Topic-name / topic-filter utilities shared by the CPU matcher and the table
compiler: level splitting, validation, `$share` parsing.

Parity surface: vendor/github.com/mochi-co/mqtt/v2/topics.go:558-624 in the
reference (isolateParticle / IsValidFilter). Re-derived from MQTT spec 4.7.
"""

from __future__ import annotations

SHARE_PREFIX = "$share"


def split_levels(topic: str) -> list[str]:
    """Split a topic/filter on '/' keeping empty levels ('a//b' -> 3 levels)."""
    return topic.split("/")


def parse_share(filter_: str) -> tuple[str, str]:
    """Return (group, inner_filter); group == '' for non-shared filters."""
    if not filter_.startswith(SHARE_PREFIX + "/"):
        return "", filter_
    rest = filter_[len(SHARE_PREFIX) + 1:]
    group, sep, inner = rest.partition("/")
    if not sep:
        return group, ""
    return group, inner


def _strip_valid_share(filter_: str, shared_allowed: bool) -> str | None:
    """For `$share/{group}/{filter}`, validate the share envelope
    [MQTT-4.8.2-1/2] and return the inner filter; None = invalid."""
    if not filter_.startswith(SHARE_PREFIX + "/"):
        return filter_
    if not shared_allowed:
        return None
    group, inner = parse_share(filter_)
    if group == "" or "+" in group or "#" in group:
        return None
    return inner or None


def valid_filter(filter_: str, shared_allowed: bool = True,
                 wildcards_allowed: bool = True) -> bool:
    """MQTT 4.7.1 filter validity, incl. `$share/{group}/{filter}` rules."""
    if filter_ == "":
        return False  # [MQTT-4.7.3-1]
    filter_ = _strip_valid_share(filter_, shared_allowed)
    if filter_ is None:
        return False
    levels = split_levels(filter_)
    for i, level in enumerate(levels):
        if "#" in level:
            if not wildcards_allowed:
                return False
            # '#' must be alone in its level and the last level [MQTT-4.7.1-2]
            if level != "#" or i != len(levels) - 1:
                return False
        elif "+" in level:
            if not wildcards_allowed:
                return False
            if level != "+":  # '+' must occupy an entire level [MQTT-4.7.1-3]
                return False
    return True


def valid_topic_name(topic: str) -> bool:
    """Publish topic names: non-empty, no wildcards [MQTT-3.3.2-2]."""
    return topic != "" and "+" not in topic and "#" not in topic


def is_dollar(topic: str) -> bool:
    """Topics beginning with '$' are excluded from root-level wildcard
    matching [MQTT-4.7.2-1]."""
    return topic.startswith("$")


def filter_matches_topic(flevels, topic_levels, dollar: bool) -> bool:
    """Exact CPU check: does a (non-`$share`) filter match a topic?

    Mirrors the trie walk semantics (vendor/github.com/mochi-co/mqtt/v2/
    topics.go:484-555): '+' matches exactly one level [MQTT-4.7.1-3], a
    trailing '#' matches the parent and anything deeper [MQTT-4.7.1.2],
    and top-level wildcards never match '$'-topics [MQTT-4.7.2-1]. Used by
    the signature matcher to verify device candidates (hash collisions are
    a perf event, never a correctness event)."""
    if not flevels:
        return False
    if dollar and flevels[0] in ("+", "#"):
        return False
    for i, fl in enumerate(flevels):
        if fl == "#":
            return True
        if i >= len(topic_levels):
            return False
        if fl != "+" and fl != topic_levels[i]:
            return False
    return len(topic_levels) == len(flevels)


UNK = 0  # token id reserved for levels never seen in any filter


def intern_level(vocab: dict[str, int], level: str) -> int:
    """Assign/look up the token id for a level string (0 reserved for UNK).
    The ONE intern rule of the table compilers, so a vocab shared by
    the shards of a mesh produces identical token ids in every shard."""
    tok = vocab.get(level)
    if tok is None:
        tok = len(vocab) + 1
        vocab[level] = tok
    return tok


def tokenize_cached(tables, topics: list[str], max_levels: int):
    """Tokenize via the C++ native tokenizer when available, else the Python
    loop. ``tables`` is an immutable compiled-table snapshot with a ``vocab``
    dict; the native vocab mirror is built once per snapshot and cached on
    it (compiles always start from a fresh vocab, so the snapshot's dict
    never mutates afterwards)."""
    nv = tables.__dict__.get("_native_vocab", False)
    if nv is False:
        nv = None
        try:
            from ..native import NativeVocab, available
            if available():
                nv = NativeVocab(tables.vocab)
        except Exception:
            nv = None
        tables.__dict__["_native_vocab"] = nv
    if nv is not None:
        return nv.tokenize(topics, max_levels)
    return tokenize_topics(tables.vocab, topics, max_levels)


def tokenize_topics(vocab: dict[str, int], topics: list[str],
                    max_levels: int):
    """Host-side topic prep shared by both compiled-table flavors: token ids
    padded with -1, lengths, $-flags. Topics deeper than max_levels report
    length -1 (engines fall back to the CPU trie)."""
    import numpy as np

    batch = len(topics)
    toks = np.full((batch, max_levels), -1, dtype=np.int32)
    lengths = np.zeros(batch, dtype=np.int32)
    dollar = np.zeros(batch, dtype=bool)
    for i, topic in enumerate(topics):
        levels = split_levels(topic)
        dollar[i] = topic.startswith("$")
        if len(levels) > max_levels:
            lengths[i] = -1
            continue
        lengths[i] = len(levels)
        for j, level in enumerate(levels):
            toks[i, j] = vocab.get(level, UNK)
    return toks, lengths, dollar


def batch_bucket(b: int) -> int:
    """Batch-axis bucket ladder shared by every device engine (ADR 006):
    16, powers of FOUR to 4096, powers of two beyond. Each bucket shape
    costs one XLA compile per table version and micro-batch sizes vary,
    so the sparse ladder trades ≤3x padding for ~3 compiles total.
    SigEngine.warm_buckets MUST walk this same ladder."""
    if b <= 16:
        return 16
    n = (b - 1).bit_length()
    if b <= 4096:
        return 1 << (n + (n & 1))
    return 1 << n


def pad_topic_batch(toks, lengths, dollar):
    """Pad a tokenized batch (toks [B, L] int, lengths [B], dollar [B])
    to its bucket with depth-0 rows (toks -1, length 0, dollar False) —
    per-topic outputs trim clean with ``[:B]``. Returns the (possibly
    padded) triple; numpy-only, usable from any engine."""
    import numpy as np

    b = len(lengths)
    bucket = batch_bucket(b)
    if bucket == b:
        return toks, lengths, dollar
    toks = np.concatenate(
        [toks, np.full((bucket - b, toks.shape[1]), -1, dtype=toks.dtype)])
    lengths = np.concatenate(
        [lengths, np.zeros(bucket - b, dtype=lengths.dtype)])
    dollar = np.concatenate(
        [dollar, np.zeros(bucket - b, dtype=dollar.dtype)])
    return toks, lengths, dollar
