"""CPU reference topic matcher: a subscription trie with full MQTT wildcard
semantics. This is both the low-latency fallback matcher and the semantic
oracle the device engines are parity-tested against.

Parity surface: vendor/github.com/mochi-co/mqtt/v2/topics.go in the reference
(TopicsIndex / particle / Subscribers / scanMessages / topic aliases).
Re-designed: recursion is over an explicit node stack, retained messages live
in the same trie, shared-group selection uses a round-robin cursor.
"""

from __future__ import annotations

import threading
from collections import deque

from ..protocol.packets import Packet, Subscription
from .topics import is_dollar, parse_share, split_levels

JOURNAL_CAP = 4096   # mutations kept for overlay replay; beyond this a
                     # matcher serves staleness via the CPU trie instead


def subs_version(index) -> int:
    """The subscription-only version of an index (falls back to the full
    version for index-likes without one): what device matchers key their
    staleness on, so retained-message churn never forces a recompile."""
    v = getattr(index, "sub_version", None)
    return v if v is not None else getattr(index, "version", 0)


class VersionedTopicCache:
    """FIFO-bounded topic -> result cache keyed on a subscription
    version: any subscribe/unsubscribe bumps the version and silently
    invalidates every entry. Shared by the broker's trie-path match
    cache and the MicroBatcher's matcher-mode cache — cached results
    are SHARED objects; consumers must treat them as immutable and
    deep_copy before mutating. ``evictions`` counts the entries a full
    cache dropped to take a new topic: a topic set larger than
    ``maxsize`` shows there, not in the hit count alone."""

    __slots__ = ("_cache", "maxsize", "evictions")

    def __init__(self, maxsize: int = 8192) -> None:
        self._cache: dict[str, tuple[int, object]] = {}
        self.maxsize = maxsize
        self.evictions = 0

    def get(self, topic: str, version: int):
        hit = self._cache.get(topic)
        if hit is not None and hit[0] == version:
            return hit[1]
        return None

    def put(self, topic: str, version: int, result) -> None:
        cache = self._cache
        if topic not in cache and len(cache) >= self.maxsize:
            cache.pop(next(iter(cache)))
            self.evictions += 1
        cache[topic] = (version, result)

    def __len__(self) -> int:
        return len(self._cache)


def merge_subscription(base: Subscription | None, new: Subscription,
                       filter_: str) -> Subscription:
    """Merge overlapping matching filters for one client: max QoS wins, v5
    subscription identifiers union (keyed by filter), flags from the newer.

    Parity: packets.go:250-270 (Subscription.Merge) in the reference.
    """
    if base is None and not new.identifier and not new.identifiers:
        # single matching filter, no v5 subscription identifier — the
        # overwhelmingly common fan-out case: no copy needed (consumers
        # never mutate the returned Subscription)
        return new
    merged = Subscription(
        filter=new.filter, qos=new.qos, no_local=new.no_local,
        retain_as_published=new.retain_as_published,
        retain_handling=new.retain_handling, identifier=new.identifier,
        identifiers=dict(new.identifiers))
    if new.identifier:
        merged.identifiers[filter_] = new.identifier
    if base is not None:
        merged.folded = base.folded + 1
        merged.identifiers.update(base.identifiers)
        if base.qos > merged.qos:
            merged.qos = base.qos
        if base.no_local:
            merged.no_local = True
    return merged


def _copy_subscription(s: Subscription) -> Subscription:
    """Field copy of one Subscription record (deep_copy's unit step)."""
    return Subscription(filter=s.filter, qos=s.qos, no_local=s.no_local,
                        retain_as_published=s.retain_as_published,
                        retain_handling=s.retain_handling,
                        identifier=s.identifier,
                        identifiers=dict(s.identifiers), folded=s.folded)


class SubscriberSet:
    """Result of a topic match: per-client merged non-shared subscriptions and
    shared-group candidate maps (group -> client -> subscription).

    A plain __slots__ class, not a dataclass: one of these is built per
    matched topic on the fan-out hot path, and slot storage makes both
    the constructor and the attribute reads measurably cheaper. When the
    maxmq_decode C extension is present, the name below is rebound to
    its C twin (same surface, C-speed construction); this class stays as
    the documented fallback and the semantic reference."""

    __slots__ = ("subscriptions", "shared")

    def __init__(self, subscriptions: dict[str, Subscription] | None = None,
                 shared: dict[tuple[str, str],
                              dict[str, Subscription]] | None = None):
        self.subscriptions = {} if subscriptions is None else subscriptions
        # (group, filter) -> client -> subscription: each pair delivers to
        # exactly one of its members [MQTT-4.8.2-4].
        self.shared = {} if shared is None else shared

    def __eq__(self, other) -> bool:
        # duck-typed (not isinstance): must hold across the C twin and
        # this fallback, and the module global is rebindable
        try:
            return (self.subscriptions == other.subscriptions
                    and self.shared == other.shared)
        except AttributeError:
            return NotImplemented

    def __repr__(self) -> str:
        return (f"SubscriberSet(subscriptions={self.subscriptions!r}, "
                f"shared={self.shared!r})")

    def add(self, client_id: str, sub: Subscription, filter_: str) -> None:
        self.subscriptions[client_id] = merge_subscription(
            self.subscriptions.get(client_id), sub, filter_)

    def deep_copy(self) -> "SubscriberSet":
        """Copies of every Subscription record. Matching aliases stored
        Subscription objects for speed; hand a hook that may mutate
        delivery parameters this copy, never the originals."""
        cp = _copy_subscription
        return SubscriberSet(
            subscriptions={c: cp(s) for c, s in self.subscriptions.items()},
            shared={k: {c: cp(s) for c, s in m.items()}
                    for k, m in self.shared.items()})

    def select_copy(self) -> "SubscriberSet":
        """Fresh outer dicts over ALIASED records — what the
        on_select_subscribers modify chain receives by default (hooks
        may add/drop/replace entries; records are immutable by
        contract, ADR 009)."""
        return SubscriberSet(
            subscriptions=dict(self.subscriptions),
            shared={k: dict(m) for k, m in self.shared.items()})

    def add_shared(self, group: str, filter_: str, client_id: str,
                   sub: Subscription) -> None:
        self.shared.setdefault((group, filter_), {})[client_id] = sub

    def __len__(self) -> int:
        return len(self.subscriptions) + sum(len(g) for g in self.shared.values())

    def resolve(self, registry: dict,
                kept: dict | None = None) -> tuple[list, dict, int, int]:
        """This result against the client registry's dict, in one pass
        (ADR 007): ``(pairs, shared, matched, resolved)``.

        ``pairs`` are the plain entries whose client id is a key of
        ``registry``, in iteration order, each already paired with the
        registry's value: ``(client, sub)``. ``shared`` is the $share
        map cut to the (group, filter) keys with at least one
        registered candidate; the member maps are this result's own,
        whole, because the round-robin cursor indexes the sorted full
        candidate set. ``matched`` counts plain entries + shared
        candidates held, ``resolved`` those with a session. Nothing is
        written onto the result: results are cached and shared.

        ``kept`` is the registry's memory of these counts, ``(group,
        filter) -> (members, len(members), hits)``, emptied by its owner
        whenever a session comes or goes (``ClientRegistry``): a key
        whose entry holds this very map at this length is not walked, so
        a group of 500 costs a publish what a group of 4 does. The
        entry's reference keeps the map's address from being reused,
        and nothing shrinks or re-keys a result's member map in place
        (``add_shared`` can only grow one), so the same object at the
        same length holds the same ids. Without ``kept`` (a bare dict,
        whose changes nobody reports) every member is counted."""
        get = registry.get
        pairs = [(client, sub) for cid, sub in self.subscriptions.items()
                 if (client := get(cid)) is not None]
        shared: dict = {}
        matched = len(self.subscriptions)
        resolved = len(pairs)
        for key, members in self.shared.items():
            n = len(members)
            matched += n
            entry = None if kept is None else kept.get(key)
            if entry is not None and entry[0] is members and entry[1] == n:
                hits = entry[2]
            else:
                hits = sum(map(registry.__contains__, members))
                if kept is not None:
                    kept[key] = (members, n, hits)
            if hits:
                resolved += hits
                shared[key] = members
        return pairs, shared, matched, resolved


_PySubscriberSet = SubscriberSet
try:
    # rebind to the C twin when the extension is ALREADY BUILT —
    # build=False keeps package import instant on fresh checkouts
    # (`make -C native` produces the .so; sig.py's device path also
    # builds it on demand, taking effect at the next interpreter)
    from ..native import decode_module as _decode_module

    _cmod = _decode_module(build=False)
    if _cmod is not None:
        _cmod.configure(merge_subscription, _copy_subscription)
        SubscriberSet = _cmod.SubscriberSet  # type: ignore[misc]
except Exception:       # any load failure keeps the python class
    pass


class _Node:
    __slots__ = ("children", "subscriptions", "shared", "retained")

    def __init__(self) -> None:
        self.children: dict[str, _Node] = {}
        self.subscriptions: dict[str, Subscription] = {}
        self.shared: dict[str, dict[str, Subscription]] = {}
        self.retained: Packet | None = None

    def empty(self) -> bool:
        return (not self.children and not self.subscriptions
                and not self.shared and self.retained is None)


class TopicIndex:
    """Thread-safe subscription + retained-message trie."""

    def __init__(self) -> None:
        self._root = _Node()
        self._lock = threading.RLock()
        self._share_cursor: dict[tuple[str, str], int] = {}
        # (group, filter) -> (the candidate map select_shared last
        # sorted, its ids in order): lives and dies with the cursor
        self._share_order: dict[tuple[str, str], tuple[dict, list]] = {}
        # select_shared calls served from a kept order, and those that
        # sorted (maxmq_broker_share_orders_{reused,sorted}_total)
        self.share_orders_reused = 0
        self.share_orders_sorted = 0
        self.subscription_count = 0
        self.retained_count = 0
        # bumped on every mutation, retained messages included
        self.version = 0
        # bumped on SUBSCRIPTION mutations only — device matchers key
        # their staleness off this so retained-message churn never forces
        # a table recompile
        self.sub_version = 0
        # journal of recent subscription mutations, so matchers can serve
        # adds/removes as a host-side overlay while a recompile runs in
        # the background: (sub_version, op '+'|'-', client_id, filter,
        # sub-or-None, group, trie_path)
        self._journal: deque = deque(maxlen=JOURNAL_CAP)

    # ------------------------------------------------------------------
    # Mutation
    # ------------------------------------------------------------------

    def subscribe(self, client_id: str, sub: Subscription) -> bool:
        """Install a subscription; returns True when it is brand new (False
        when it replaced an existing subscription of the same client+filter)."""
        group, inner = parse_share(sub.filter)
        levels = split_levels(inner if group else sub.filter)
        with self._lock:
            node = self._root
            for level in levels:
                node = node.children.setdefault(level, _Node())
            if group:
                holders = node.shared.setdefault(group, {})
                is_new = client_id not in holders
                holders[client_id] = sub
            else:
                is_new = client_id not in node.subscriptions
                node.subscriptions[client_id] = sub
            if is_new:
                self.subscription_count += 1
            self.version += 1
            self.sub_version += 1
            self._journal.append((self.sub_version, "+", client_id,
                                  sub.filter, sub, group,
                                  "/".join(levels)))
            return is_new

    def unsubscribe(self, client_id: str, filter_: str) -> bool:
        group, inner = parse_share(filter_)
        levels = split_levels(inner if group else filter_)
        with self._lock:
            path: list[tuple[_Node, str]] = []
            node = self._root
            for level in levels:
                child = node.children.get(level)
                if child is None:
                    return False
                path.append((node, level))
                node = child
            if group:
                holders = node.shared.get(group)
                if not holders or client_id not in holders:
                    return False
                sub_filter = holders[client_id].filter
                del holders[client_id]
                if not holders:
                    del node.shared[group]
                    self._share_cursor.pop((group, sub_filter), None)
                    self._share_order.pop((group, sub_filter), None)
            else:
                if client_id not in node.subscriptions:
                    return False
                del node.subscriptions[client_id]
            self.subscription_count -= 1
            self._trim(path, node)
            self.version += 1
            self.sub_version += 1
            self._journal.append((self.sub_version, "-", client_id,
                                  filter_, None, group, "/".join(levels)))
            return True

    def _trim(self, path: list[tuple[_Node, str]], node: _Node) -> None:
        for parent, level in reversed(path):
            if node.empty():
                del parent.children[level]
                node = parent
            else:
                return

    def journal_since(self, version: int):
        """Subscription mutations after ``version`` in order, or None when
        the journal no longer reaches back that far (the caller must do a
        full resync). Entries: (sub_version, op, client_id, filter, sub,
        group, trie_path)."""
        with self._lock:
            if version >= self.sub_version:
                return []
            # versions are consecutive: scan from the newest end and stop
            # at the first already-applied entry (O(new), not O(cap))
            entries = []
            for e in reversed(self._journal):
                if e[0] <= version:
                    break
                entries.append(e)
            entries.reverse()
            if not entries or entries[0][0] != version + 1:
                return None
            return entries

    # ------------------------------------------------------------------
    # Matching
    # ------------------------------------------------------------------

    def walk_subscriptions(self):
        """Yield every installed (client_id, Subscription) pair, shared
        ones with their original ``$share/group/...`` filter. Snapshot
        semantics under the index lock; used to seed external matchers
        (the matcher service) with pre-existing state."""
        with self._lock:
            out = []
            stack = [self._root]
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                out.extend(node.subscriptions.items())
                for holders in node.shared.values():
                    out.extend(holders.items())
        yield from out

    def subscribers(self, topic: str) -> SubscriberSet:
        """All subscriptions matching a published topic name.

        Per level the walk tries the literal child, '+', and '#'; a '#' child
        also matches the parent level itself (spec 4.7.1.2), and topics whose
        first level begins with '$' never match root-level wildcards
        [MQTT-4.7.2-1].
        """
        levels = split_levels(topic)
        out = SubscriberSet()
        dollar = is_dollar(topic)
        with self._lock:
            # stack of (node, depth): node's path matches levels[:depth]
            stack: list[tuple[_Node, int]] = [(self._root, 0)]
            while stack:
                node, depth = stack.pop()
                wildcard_ok = not (dollar and depth == 0)
                if wildcard_ok:
                    hash_child = node.children.get("#")
                    if hash_child is not None:
                        self._collect(out, hash_child)
                if depth == len(levels):
                    self._collect(out, node)
                    continue
                lit = node.children.get(levels[depth])
                if lit is not None:
                    stack.append((lit, depth + 1))
                if wildcard_ok:
                    plus = node.children.get("+")
                    if plus is not None:
                        stack.append((plus, depth + 1))
        return out

    def _collect(self, out: SubscriberSet, node: _Node) -> None:
        for client_id, sub in node.subscriptions.items():
            out.add(client_id, sub, sub.filter)
        for group, holders in node.shared.items():
            for client_id, sub in holders.items():
                out.add_shared(group, sub.filter, client_id, sub)

    def select_shared(self, group: str, filter_: str,
                      candidates: dict[str, Subscription],
                      alive=None) -> tuple[str, Subscription] | None:
        """Pick one receiver for a `$share` (group, filter) pair: round-robin
        over the sorted candidate set, skipping clients rejected by the
        ``alive`` predicate.

        The reference picks effectively-arbitrarily (map iteration order,
        topics.go:255-270); round-robin gives fairer load spreading.

        The order is ``sorted(candidates)`` for every input, sorted
        once a map and not once a message: the key keeps the map it last
        sorted beside its ids in order, and a pick asked about that very
        map at that length is the cursor, the next index and ``alive``.
        The native decode hands out one immutable map a table row; a
        map not seen before (the trie's ``_collect`` and a hook's
        ``select_copy`` build a fresh dict a result) is sorted as it
        always was and becomes the kept one. Why identity and length
        decide: ``SubscriberSet.resolve``, ADR 007.
        """
        if not candidates:
            return None
        key = (group, filter_)
        with self._lock:
            kept = self._share_order.get(key)
            if (kept is not None and kept[0] is candidates
                    and len(kept[1]) == len(candidates)):
                ordered = kept[1]
                self.share_orders_reused += 1
            else:
                ordered = sorted(candidates)
                self._share_order[key] = (candidates, ordered)
                self.share_orders_sorted += 1
            n = len(ordered)
            idx = self._share_cursor.get(key, -1)
            for _ in range(n):
                idx = (idx + 1) % n
                cid = ordered[idx]
                if alive is None or alive(cid):
                    self._share_cursor[key] = idx
                    return cid, candidates[cid]
        return None

    # ------------------------------------------------------------------
    # Retained messages
    # ------------------------------------------------------------------

    def retain(self, packet: Packet) -> int:
        """Store/replace/clear the retained message for packet.topic.
        Returns +1 stored-new, 0 replaced, -1 cleared (empty payload)."""
        levels = split_levels(packet.topic)
        with self._lock:
            if not packet.payload:
                # clearing walk; avoid creating nodes
                path: list[tuple[_Node, str]] = []
                node = self._root
                for level in levels:
                    child = node.children.get(level)
                    if child is None:
                        return 0
                    path.append((node, level))
                    node = child
                if node.retained is None:
                    return 0
                node.retained = None
                self.retained_count -= 1
                self._trim(path, node)
                self.version += 1
                return -1
            node = self._root
            for level in levels:
                node = node.children.setdefault(level, _Node())
            existed = node.retained is not None
            node.retained = packet
            if not existed:
                self.retained_count += 1
            self.version += 1
            return 0 if existed else 1

    def retained_get(self, topic: str) -> Packet | None:
        """Exact-topic retained lookup (no wildcard expansion)."""
        with self._lock:
            node = self._root
            for level in split_levels(topic):
                node = node.children.get(level)
                if node is None:
                    return None
            return node.retained

    def retained_for(self, filter_: str) -> list[Packet]:
        """Retained messages matching a subscription filter (wildcard-aware;
        '#'/'+' at the first level skip '$' topics [MQTT-4.7.2-1])."""
        levels = split_levels(filter_)
        out: list[Packet] = []
        with self._lock:
            self._scan_retained(self._root, levels, 0, out)
        out.sort(key=lambda p: p.created)
        return out

    def _scan_retained(self, node: _Node, levels: list[str], depth: int,
                       out: list[Packet]) -> None:
        if depth == len(levels):
            if node.retained is not None:
                out.append(node.retained)
            return
        level = levels[depth]
        if level == "#":
            self._collect_subtree_retained(node, depth == 0, out)
            return
        if level == "+":
            for name, child in node.children.items():
                if depth == 0 and name.startswith("$"):
                    continue
                self._scan_retained(child, levels, depth + 1, out)
            return
        child = node.children.get(level)
        if child is not None:
            self._scan_retained(child, levels, depth + 1, out)

    @staticmethod
    def _collect_subtree_retained(node: _Node, top: bool,
                                  out: list[Packet]) -> None:
        """'#' matches the parent level itself and every descendant;
        top-level '$' children are excluded [MQTT-4.7.2-1]."""
        stack = [(node, top)]
        while stack:
            n, top = stack.pop()
            if n.retained is not None:
                out.append(n.retained)
            for name, child in n.children.items():
                if top and name.startswith("$"):
                    continue
                stack.append((child, False))

    # ------------------------------------------------------------------
    # Introspection (table compiler input, $SYS counters)
    # ------------------------------------------------------------------

    def all_subscriptions(self) -> list[tuple[str, str, Subscription, str]]:
        """All (filter, client_id, subscription, group) entries, materialized
        under the lock so callers iterate a stable snapshot. ``group`` is ''
        for non-shared. Used by the table compilers (sig.compile_sig)."""
        out: list[tuple[str, str, Subscription, str]] = []
        with self._lock:
            stack: list[tuple[_Node, list[str]]] = [(self._root, [])]
            while stack:
                node, path = stack.pop()
                filt = "/".join(path)
                for client_id, sub in node.subscriptions.items():
                    out.append((filt, client_id, sub, ""))
                for group, holders in node.shared.items():
                    for client_id, sub in holders.items():
                        out.append((filt, client_id, sub, group))
                for name, child in node.children.items():
                    stack.append((child, path + [name]))
        return out


class TopicAliases:
    """Per-client inbound/outbound v5 topic alias maps.

    Parity: topics.go:21-105 in the reference.
    """

    def __init__(self, maximum: int) -> None:
        self.maximum = maximum
        self.inbound: dict[int, str] = {}
        self.outbound: dict[str, int] = {}
        self._next_out = 0

    def resolve_inbound(self, topic: str, alias: int | None) -> str | None:
        """Apply/learn an inbound alias; None means the alias is invalid."""
        if alias is None:
            return topic
        if alias == 0 or alias > self.maximum:
            return None
        if topic:
            self.inbound[alias] = topic
            return topic
        return self.inbound.get(alias)

    def assign_outbound(self, topic: str) -> tuple[int, bool]:
        """Return (alias, first_use). alias 0 = no alias available."""
        if self.maximum <= 0:
            return 0, False
        existing = self.outbound.get(topic)
        if existing is not None:
            return existing, False
        if self._next_out >= self.maximum:
            return 0, False
        self._next_out += 1
        self.outbound[topic] = self._next_out
        return self._next_out, True
