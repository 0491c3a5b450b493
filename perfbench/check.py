"""The comparison that decides ``correct``: what the generators sent and
what their subscribers got, joined and held against the plain reference
(the expected receivers ride each sent record, computed by
``reference.Reference`` in the generator that sent it). Where clients
subscribed and unsubscribed during the phase, ``Churn`` says which of
them a message had to reach, could reach, and must not reach."""

from __future__ import annotations

from reference import match_levels

NEVER_NS = 1 << 62      # an acknowledgement that never came: no bound


class Churn:
    """The churn clients' subscriptions over a phase, as intervals, and
    the rule that holds a delivery to them (MQTT 3.1.1 sections 3.8.4,
    3.10.4). A message is matched at one instant in ``[p0, a1]``: ``p0``
    its PUBLISH sent, ``a1`` its first arrival at any receiver, or the
    drain's end where it reached nobody. A subscription is in force from
    an instant in ``[s0, s1]`` (SUBSCRIBE sent, SUBACK received) to one in
    ``[u0, u1]`` (UNSUBSCRIBE sent, UNSUBACK received). So a client *must*
    get the message through one of its subscriptions if ``s1 <= p0`` and
    ``u0 >= a1``; *must not* through it if ``s0 >= a1`` or ``u1 <= p0``;
    *may* otherwise. ``churn``: ``{"log": [(cid, filter, qos, "sub" |
    "unsub", sent_ns, acked_ns)], "drain_end_ns": ns}`` merged over the
    generators."""

    def __init__(self, churn: dict) -> None:
        self.drain_end = churn["drain_end_ns"]
        self.unacked = self.orphans = 0
        spans: dict = {}            # (cid, filter) -> [[s0, s1, u0, u1]]
        qos: dict = {}
        for cid, filt, q, kind, sent, acked in sorted(
                churn["log"], key=lambda r: r[4]):
            if acked is None:
                self.unacked += 1
                acked = NEVER_NS
            if kind == "sub":
                spans.setdefault((cid, filt), []).append(
                    [sent, acked, NEVER_NS, NEVER_NS])
                qos[cid] = q
            elif spans.get((cid, filt)) and \
                    spans[(cid, filt)][-1][2] == NEVER_NS:
                spans[(cid, filt)][-1][2:] = [sent, acked]
            else:
                self.orphans += 1   # an UNSUBSCRIBE of nothing held
        # a first-level index over the few hundred filters, and a memo
        # by topic: (cid, qos, intervals) of every matching subscription
        self.first: dict = {}
        for (cid, filt), ivs in spans.items():
            levels = filt.split("/")
            key = levels[0] if levels[0] not in ("+", "#") else None
            self.first.setdefault(key, []).append(
                (levels, cid, qos[cid], ivs))
        self.memo: dict = {}

    def matching(self, topic: str) -> list:
        hit = self.memo.get(topic)
        if hit is None:
            levels = topic.split("/")
            hit = self.memo[topic] = [
                (cid, q, ivs) for f, cid, q, ivs in
                self.first.get(levels[0], []) + self.first.get(None, [])
                if match_levels(f, levels)]
        return hit

    def due(self, topic: str, qos: int, p0: int, a1: int) -> tuple:
        """(client -> QoS it must get the message at, client -> QoS it
        may get it at) for one message."""
        must: dict = {}
        may: dict = {}
        for cid, q, ivs in self.matching(topic):
            for s0, s1, u0, u1 in ivs:
                if s1 <= p0 and u0 >= a1:
                    must[cid] = min(qos, q)
                elif s0 < a1 and u1 > p0:
                    may[cid] = min(qos, q)
        for cid in must:
            may.pop(cid, None)
        return must, may


def join(dumps: list[dict], groups: dict, t0: int, t1: int,
         churn: dict | None = None) -> dict:
    """``dumps``: one per generator process, as ``loadgen`` wrote them.
    ``groups``: share group -> member ids. ``t0``/``t1``: the window on
    the host's monotonic clock, in ns. ``churn``: the churn clients' log
    (``Churn``), where the traffic has them. Returns the counts the last
    line needs, the latencies of every right delivery (arrival - due, ns)
    with their due times beside them, how many of them arrived inside the window, and the failures that
    make a run incorrect."""
    members = {cid: g for g, ms in groups.items() for cid in ms}
    held = Churn(churn) if churn is not None else None
    sent: dict = {}
    first_seq: dict = {}
    for d in dumps:
        for rec in d["sent"]:
            sent[(rec[0], rec[1])] = rec
            if rec[1] < first_seq.get(rec[0], 1 << 62):
                first_seq[rec[0]] = rec[1]
    have: dict = {}
    disorder, strays = [], 0
    for d in dumps:
        for cid, recs in d["got"].items():
            last: dict = {}
            for head, arrival, flags in recs:
                pub, seq, _due = (int(x) for x in head.split(b":"))
                if seq <= last.get(pub, -1):
                    disorder.append((cid, pub, last[pub], seq))
                else:
                    last[pub] = seq
                if (pub, seq) in sent:
                    have.setdefault((pub, seq), []).append(
                        (cid, flags & 3, arrival))
                elif seq >= first_seq.get(pub, 0):
                    strays += 1     # (older: a straggler of the warm-up)
    attempted = failed = lost_qos1 = in_window = 0
    churn_must = churn_may = 0
    wrong, latencies, dues = [], [], []
    for key, rec in sent.items():
        _pub, _seq, topic, qos, due, sent_ns, plain, shared = rec
        want = {cid: min(qos, q) for cid, q in plain.items()}
        may: dict = {}
        if held is not None:
            a1 = min((a for _c, _q, a in have.get(key, ())),
                     default=held.drain_end)
            must, may = held.due(topic, qos, sent_ns, a1)
            want.update(must)
            churn_must += len(must)
            churn_may += len(may)
        attempted += len(want) + len(shared) + (1 if qos else 0)
        seen_groups: dict = {}
        bad = False
        for cid, q, arrival in have.get(key, ()):
            group = members.get(cid)
            if cid in want:
                bad |= want.pop(cid) != q
            elif cid in may:
                bad |= may.pop(cid) != q
            elif (group in shared and cid in shared[group]
                  and group not in seen_groups):
                seen_groups[group] = cid
                bad |= q != min(qos, shared[group][cid])
            else:
                bad = True          # nobody's, a second copy, or a
                continue            # group served twice
            latencies.append(arrival - due)
            dues.append(due)
            in_window += t0 <= arrival < t1
        missing = [(cid, q) for cid, q in want.items()]
        missing += [(g, min(qos, max(ms.values())))
                    for g, ms in shared.items() if g not in seen_groups]
        failed += len(missing)
        # a QoS 0 delivery the overload ladder shed is a failed
        # operation; a QoS 1 delivery that never came is a lost message
        lost_qos1 += sum(1 for _who, q in missing if q)
        if bad:
            wrong.append((topic, sorted(have.get(key, ()))[:4]))
    unacked = sum(len(v) for d in dumps for v in d["unacked"].values())
    failed += unacked
    failures = []
    if wrong:
        failures.append(f"{len(wrong)} messages delivered to a wrong set, "
                        f"first {wrong[:2]}")
    if strays:
        failures.append(f"{strays} deliveries of messages nobody sent")
    if disorder:
        failures.append(f"per-publisher order broken {len(disorder)} times "
                        f"(or a duplicate), first {disorder[:2]}")
    if lost_qos1:
        failures.append(f"{lost_qos1} QoS 1 deliveries never arrived")
    if unacked:
        failures.append(f"{unacked} QoS 1 PUBLISHes never PUBACKed")
    out = {"attempted": attempted, "failed": failed,
           "messages": len(sent), "deliveries": len(latencies),
           "in_window": in_window, "latencies_ns": latencies,
           "due_ns": dues,
           "failures": failures}
    if held is not None:
        if held.unacked:
            failures.append(f"{held.unacked} SUBSCRIBEs or UNSUBSCRIBEs "
                            "never acknowledged")
        if held.orphans:
            failures.append(f"{held.orphans} UNSUBSCRIBEs of a filter not "
                            "held")
        # churn deliveries held to "must" and left to "may"
        out["churn"] = {"must": churn_must, "may": churn_may}
    return out
