"""The comparison that decides ``correct``: what the generators sent and
what their subscribers got, joined and held against the plain reference
(the expected receivers ride each sent record, computed by
``reference.Reference`` in the generator that sent it)."""

from __future__ import annotations


def join(dumps: list[dict], groups: dict, t0: int, t1: int) -> dict:
    """``dumps``: one per generator process, as ``loadgen`` wrote them.
    ``groups``: share group -> member ids. ``t0``/``t1``: the window on
    the host's monotonic clock, in ns. Returns the counts the last line
    needs, the latencies of every right delivery (arrival - due, ns)
    with their due times beside them, how many of them arrived inside the window, and the failures that
    make a run incorrect."""
    members = {cid: g for g, ms in groups.items() for cid in ms}
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
    wrong, latencies, dues = [], [], []
    for key, rec in sent.items():
        _pub, _seq, topic, qos, due, _sent_ns, plain, shared = rec
        want = {cid: min(qos, q) for cid, q in plain.items()}
        attempted += len(want) + len(shared) + (1 if qos else 0)
        seen_groups: dict = {}
        bad = False
        for cid, q, arrival in have.get(key, ()):
            group = members.get(cid)
            if cid in want:
                bad |= want.pop(cid) != q
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
    return {"attempted": attempted, "failed": failed,
            "messages": len(sent), "deliveries": len(latencies),
            "in_window": in_window, "latencies_ns": latencies,
            "due_ns": dues,
            "failures": failures}
