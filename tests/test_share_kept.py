"""What is O(members) of a ``$share`` key is done when the group or the
registry changes, not once a message (ADR 007, PR 37): the sorted order
``TopicIndex.select_shared`` keeps a (group, filter) key and the count
of members with a session ``ClientRegistry.resolve`` keeps. Held, step
by step over random histories, against the plain implementation below
(``sorted(candidates)`` + cursor; a count by ``in``), in every form a
match result takes: the Python set, the native decode's set and its
DeliveryIntents (tests/test_fanout_resolve.py's MODES drive the same
three through a served broker)."""

import random

import pytest

from maxmq_tpu.broker.client import ClientRegistry
from maxmq_tpu.matching import TopicIndex
from maxmq_tpu.matching.trie import _PySubscriberSet
from maxmq_tpu.protocol import Subscription

FORMS = ["py_set", "c_set", "intents"]
TOPIC = "job/1"
SHARED = ["$share/g/job/#", "$share/g/job/+", "$share/h/job/#"]
PLAIN = ["job/#", "job/1"]
IDS = [f"m{i:02d}" for i in range(12)]


class _Session:
    """Stands for a Client: an id, and whether its socket is gone."""

    def __init__(self, cid: str) -> None:
        self.id, self.closed = cid, False


def plain_select(cursors: dict, key, candidates: dict, alive):
    """``select_shared`` as it was before an order was kept."""
    ordered = sorted(candidates)
    cur = cursors.get(key, -1)
    for i in range(1, len(ordered) + 1):
        idx = (cur + i) % len(ordered)
        if alive(ordered[idx]):
            cursors[key] = idx
            return ordered[idx], candidates[ordered[idx]]
    return None


def plain_resolve(result, sessions: dict):
    """``resolve`` by ``in``, from what the result holds."""
    entries = (list(result) if hasattr(result, "to_set")
               else list(result.subscriptions.items()))
    pairs = [(sessions[cid], sub) for cid, sub in entries if cid in sessions]
    shared = {k: m for k, m in result.shared.items()
              if any(cid in sessions for cid in m)}
    matched = len(entries) + sum(len(m) for m in result.shared.values())
    resolved = len(pairs) + sum(cid in sessions
                                for m in result.shared.values() for cid in m)
    return pairs, shared, matched, resolved


class World:
    """An index, a registry and, for the native forms, an engine over
    the index; beside them the plain model's cursors and sessions."""

    def __init__(self, form: str) -> None:
        self.index = TopicIndex()
        self.registry = ClientRegistry()
        self.sessions: dict = {}        # the model's registry
        self.cursors: dict = {}         # the model's _share_cursor
        self.held: dict = {}            # (group, filter) -> member ids
        self.last = None                # the result asked about last
        self.engine = None
        if form != "py_set":
            from maxmq_tpu.matching.sig import SigEngine
            from maxmq_tpu.native import decode_module
            mod = decode_module()
            if mod is None or not hasattr(mod, "DeliveryIntents"):
                pytest.skip("maxmq_decode extension unavailable")
            self.engine = SigEngine(self.index, auto_refresh=False)
            self.engine.emit_intents = form == "intents"
            self.engine.route_small = False

    # -- what changes the group or the registry ------------------------

    def subscribe(self, cid: str, filt: str, qos: int) -> None:
        self.index.subscribe(cid, Subscription(filter=filt, qos=qos))
        if filt in SHARED:
            self.held.setdefault((filt.split("/")[1], filt), set()).add(cid)
        self.last = None

    def unsubscribe(self, cid: str, filt: str) -> None:
        if not self.index.unsubscribe(cid, filt):
            return
        self.last = None
        key = (filt.split("/")[1], filt)
        if filt in SHARED:
            self.held[key].discard(cid)
            if not self.held[key]:      # the cursor goes with the group
                self.cursors.pop(key, None)
                assert key not in self.index._share_order

    def add(self, cid: str) -> None:
        session = _Session(cid)
        self.registry.add(session)
        self.sessions[cid] = session

    def delete(self, cid: str) -> None:
        self.registry.delete(cid)
        self.sessions.pop(cid, None)

    # -- a publish ------------------------------------------------------

    def result(self, fresh: bool):
        """The answer for TOPIC in this world's form: the one asked
        about last, or a fresh one that is equal to it."""
        if not fresh and self.last is not None:
            return self.last
        if self.engine is None:
            walked = self.index.subscribers(TOPIC)
            got = _PySubscriberSet(dict(walked.subscriptions),
                                   {k: dict(m)
                                    for k, m in walked.shared.items()})
        else:
            if fresh:                   # a rotation: new rows, new maps
                self.engine.refresh(force=True)
            # stale tables answer through the overlay: sets the Python
            # union has just built, whatever the form
            (got,) = self.engine.subscribers_host_batch([TOPIC])
        self.last = got
        return got

    def alive(self, cid: str) -> bool:
        session = self.sessions.get(cid)
        return session is not None and not session.closed

    def publish(self, fresh: bool) -> None:
        result = self.result(fresh)
        truth = self.index.subscribers(TOPIC)
        assert dict(result.shared) == dict(truth.shared)
        want = plain_resolve(result, self.sessions)
        got = self.registry.resolve(result)
        assert [(c, id(s)) for c, s in got[0]] == \
            [(c, id(s)) for c, s in want[0]]
        assert list(got[1]) == list(want[1])            # order too
        assert all(got[1][k] is result.shared[k] for k in got[1])
        assert got[2:] == want[2:]
        for (group, filt), candidates in got[1].items():
            pick = self.index.select_shared(group, filt, candidates,
                                            self.alive)
            assert pick == plain_select(self.cursors, (group, filt),
                                        candidates, self.alive)
            assert self.index._share_cursor == self.cursors


def random_step(world: World, rng: random.Random) -> None:
    """One to three changes (so that a map can come back at its old
    length with other ids), then one to four publishes."""
    for _ in range(rng.randint(1, 3)):
        cid = rng.choice(IDS)
        op = rng.random()
        if op < 0.2:
            world.subscribe(cid, rng.choice(SHARED + PLAIN),
                            rng.randint(0, 2))
        elif op < 0.4:
            world.unsubscribe(cid, rng.choice(SHARED + PLAIN))
        elif op < 0.5:
            world.add(cid)
        elif op < 0.57:
            world.delete(cid)
        elif op < 0.67 and cid in world.sessions:
            world.sessions[cid].closed = not world.sessions[cid].closed
    for _ in range(rng.randint(1, 4)):      # the same map again, mostly
        world.publish(fresh=rng.random() < 0.25)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("form", FORMS)
def test_picks_cursors_and_counts_are_the_plain_ones(form, seed):
    rng = random.Random(f"{form}/{seed}")
    world = World(form)
    for cid in IDS[:8]:
        world.subscribe(cid, SHARED[0], 1)
    for cid in IDS[:5]:
        world.add(cid)
    for _ in range(120):
        random_step(world, rng)
    index = world.index
    assert index.share_orders_reused > index.share_orders_sorted > 0


@pytest.mark.parametrize("form", FORMS)
def test_a_kept_order_goes_with_its_cursor_on_the_last_unsubscribe(form):
    world = World(form)
    key = ("g", SHARED[0])
    for cid in IDS[:3]:
        world.subscribe(cid, SHARED[0], 1)
        world.add(cid)
    world.publish(fresh=True)
    world.publish(fresh=False)
    assert world.index._share_cursor[key] == 1
    assert sorted(world.index._share_order[key][1]) == IDS[:3]
    world.unsubscribe(IDS[0], SHARED[0])
    world.unsubscribe(IDS[1], SHARED[0])
    assert key in world.index._share_order      # a member is left
    world.unsubscribe(IDS[2], SHARED[0])
    assert key not in world.index._share_order
    assert key not in world.index._share_cursor
    world.subscribe(IDS[4], SHARED[0], 1)       # the group comes back:
    world.add(IDS[4])                           # the rotation starts over
    world.publish(fresh=True)
    assert world.index._share_cursor == {key: 0} == world.cursors


def test_ten_thousand_picks_from_500_members_sort_once():
    filt = "$share/ingest/fleet/telemetry/#"
    index, registry = TopicIndex(), ClientRegistry()
    members = {f"ingest-{i}": Subscription(filter=filt, qos=1)
               for i in range(500)}
    for cid in members:
        registry.add(_Session(cid))
    result = _PySubscriberSet({}, {("ingest", filt): members})
    ordered = sorted(members)
    for i in range(10_000):
        _pairs, shared, matched, resolved = registry.resolve(result)
        assert (matched, resolved) == (500, 500)
        cid, _sub = index.select_shared(
            "ingest", filt, shared[("ingest", filt)],
            alive=lambda cid: not registry.get(cid).closed)
        assert cid == ordered[i % 500]
    assert (index.share_orders_sorted, index.share_orders_reused) == \
        (1, 9_999)
    # the registry walked the 500 once, and does again once it changes
    assert registry._share_hits[("ingest", filt)] == (members, 500, 500)
    registry.delete("ingest-7")
    assert registry._share_hits == {}
    assert registry.resolve(result)[2:] == (500, 499)
    # an equal map that is another object is sorted anew and kept
    again = dict(members)
    assert index.select_shared("ingest", filt, again)[0] == ordered[0]
    assert index.share_orders_sorted == 2
    assert index._share_order[("ingest", filt)][0] is again
    # the same object, grown in place: its length gives it away
    again["ingest-0000"] = members["ingest-0"]
    assert index.select_shared("ingest", filt, again)[0] == "ingest-0000"
    assert index.share_orders_sorted == 3
    assert registry.resolve(_PySubscriberSet(
        {}, {("ingest", filt): again}))[2:] == (501, 499)


def test_a_bare_dict_registry_is_counted_every_time():
    """No ``kept``: nobody reports such a registry's changes, so
    ``resolve`` reads it live (ADR 007's immutability rule)."""
    filt = "$share/g/job/#"
    members = {cid: Subscription(filter=filt, qos=0) for cid in IDS}
    sessions = {cid: _Session(cid) for cid in IDS[:4]}
    from maxmq_tpu.matching.trie import SubscriberSet
    for cls in (_PySubscriberSet, SubscriberSet):
        result = cls({}, {("g", filt): members})
        live = dict(sessions)
        assert result.resolve(live)[2:] == (12, 4)
        del live[IDS[0]]
        assert result.resolve(live)[2:] == (12, 3)
        kept: dict = {}
        assert result.resolve(live, kept)[2:] == (12, 3)
        assert kept == {("g", filt): (members, 12, 3)}
        if cls is not _PySubscriberSet:         # the C twin checks
            with pytest.raises(TypeError):
                result.resolve(live, [])
