"""The comparison that decides ``correct``, and the end-to-end metrics.

Every request of the window is judged against the plain reference over
the ids that were live for the whole time it ran and that its filter
admits; ids an update touched meanwhile count neither way, and an id the
filter does not admit is a bad id. The numbers compared, each with its limit:

- ``unanswered``: requests of the window that failed, never came, or
  came back without k ids and distances for each query (0);
- ``bad_ids``: returned ids that were dead, deleted or out of range (0);
- ``recall_at_10``: mean recall@k over every answered query, at least
  the configuration's floor;
- ``dist_gap``: the worst gap between a returned distance and the float64
  truth, over the fp32 rounding bound, at most the configuration's limit;
- with an update stream, ``fresh_top1``: the share of probes of inserted
  vectors whose first answer is the reference's, at least the cell's
  limit, and ``failed_updates`` (0).
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

from bench.reference import judge
from bench.window import latencies_ms, nearest_rank


def _in_window(r, t0, t1):
    return t0 <= r.due < t1


def _well_formed(r, k):
    """One row of k ids and k distances per query."""
    shape = (len(r.queries), k)
    return np.shape(r.ids) == shape and np.shape(r.dists) == shape


def judge_window(cell, config, ref, reqs, warm_ops, ops, n0, t0, t1):
    k = config["k"]
    g = config["guarantees"]
    all_ops = sorted(warm_ops + ops, key=lambda o: o.start)
    acks = np.array([o.ack if o.ack is not None else np.inf
                     for o in all_ops])
    starts = np.array([o.start for o in all_ops])
    window = [r for r in reqs if _in_window(r, t0, t1)]
    answered = [r for r in window if r.answered and _well_formed(r, k)]
    groups = defaultdict(list)
    masks = {}
    for r in answered:
        v0 = int(np.searchsorted(acks, r.sent, side="right"))
        v1 = int(np.searchsorted(starts, r.done, side="left"))
        fkey = None if r.allowed is None else id(r.allowed)
        masks[fkey] = r.allowed
        groups[(v0, max(v0, v1), fkey)].append(r)

    cap = len(ref.host)
    live = np.zeros(cap, bool)
    live[:n0] = True
    applied = 0
    recalls, bad, gap = [], 0, 0.0
    probes = hits = 0
    for (v0, v1, fkey) in sorted(groups, key=lambda g: g[:2]):
        for o in all_ops[applied:v0]:
            live[o.ids] = o.kind == "insert"
        applied = v0
        changed = np.zeros(cap, bool)
        for o in all_ops[v0:v1]:
            changed[o.ids] = True
        stable = live & ~changed
        if masks[fkey] is not None:
            stable &= masks[fkey]
            changed &= masks[fkey]
        rs = groups[(v0, v1, fkey)]
        q = np.concatenate([r.queries for r in rs])
        ids = np.concatenate([np.asarray(r.ids, np.int64) for r in rs])
        ds = np.concatenate([np.asarray(r.dists) for r in rs])
        rec, b, w, top1 = judge(ref, q, ids, ds, stable, changed, k)
        recalls.append(rec)
        bad += b
        gap = max(gap, w)
        off = 0
        for r in rs:
            n = len(r.queries)
            if r.probe_ids is not None:
                p = len(r.probe_ids)
                rows = slice(off + n - p, off + n)
                ok = stable[r.probe_ids]
                probes += int(ok.sum())
                hits += int((ids[rows, 0] == top1[rows])[ok].sum())
            off += n
    recall = float(np.concatenate(recalls).mean()) if recalls else 0.0
    failed_ops = sum(o.error is not None for o in ops)
    checks = {
        "unanswered": {"value": len(window) - len(answered), "limit": "== 0"},
        "bad_ids": {"value": bad, "limit": "== 0"},
        "recall_at_10": {"value": recall,
                         "limit": f">= {g['recall_at_10_min']}"},
        "dist_gap": {"value": gap,
                     "limit": f"<= {g['distance_gap_over_fp32_bound_max']}"},
    }
    passed = (checks["unanswered"]["value"] == 0 and bad == 0
              and recall >= g["recall_at_10_min"]
              and gap <= g["distance_gap_over_fp32_bound_max"]
              and len(answered) > 0)
    fresh_min = cell.spec.get("limits", {}).get("fresh_top1_min")
    if fresh_min is not None:
        fresh = hits / probes if probes else 0.0
        checks["fresh_top1"] = {"value": fresh, "limit": f">= {fresh_min}"}
        checks["failed_updates"] = {"value": failed_ops, "limit": "== 0"}
        passed = passed and fresh >= fresh_min and failed_ops == 0
    in_ops = [o for o in ops if t0 <= o.start < t1]
    return {"correct": bool(passed),
            "attempted": len(window) + len(in_ops),
            "failed": len(window) - len(answered) + failed_ops,
            "recall": recall, "checks": checks}


def end_to_end(reqs, ops, verdict, t0, t1, setup_s, give_up, log):
    """The end-to-end metrics of the window, by the host's clock."""
    seconds = t1 - t0
    window = [r for r in reqs if _in_window(r, t0, t1)]
    lat = latencies_ms(reqs, t0, t1, give_up)
    done_rows = sum(len(r.queries) for r in window
                    if r.answered and r.done <= t1)
    updated = sum(len(o.ids) for o in ops if o.error is None
                  and o.ack is not None and t0 <= o.start and o.ack <= t1)
    late = [r.sent - r.due for r in window if r.sent is not None]
    out = {"p50_ms": nearest_rank(lat, 50), "p99_ms": nearest_rank(lat, 99),
           "qps": done_rows / seconds,
           "recall_at_10": verdict["recall"],
           "updates_per_s": updated / seconds, "setup_s": setup_s}
    log(f"[window] requests={len(window)} p50_ms={out['p50_ms']} "
        f"p99_ms={out['p99_ms']} qps={out['qps']} "
        f"updates_per_s={out['updates_per_s']} "
        f"generator_late_ms_p50={nearest_rank(late, 50) * 1e3} "
        f"generator_late_ms_p99={nearest_rank(late, 99) * 1e3} "
        f"generator_late_ms_max={max(late, default=0.0) * 1e3}")
    return out
