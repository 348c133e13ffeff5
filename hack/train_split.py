#!/usr/bin/env python3
"""A window's starved time by what each record FOLLOWS, and where its rows' tokens came from (ISSUE 40).

    python3 hack/train_split.py <run dir>/timeline.json [...]

Reads what ``hack/timeline_run.py`` keeps. The records of the window are put
in the order of their dispatch (``submit_ts``, else ``start_ts``) and every
record's ``starved_ms`` goes to one of four classes:

  (i)   a ``mixed`` record whose dispatch follows a ``mixed`` record's;
  (ii)  the first ``mixed`` record behind a drain (it follows a ``decode`` or
        ``prefill`` record, or nothing);
  (iii) a fresh ``decode`` submit (``h2d_arrays`` 1) that follows a ``mixed`` record;
  (iv)  the rest.

(i) and (iii) are the dispatches that wait for a mixed step whose result the
host does not need in order to build them: what an admission train that runs
one step ahead of the host takes off the critical path. On a tree that has
``tokens_from`` on its records, the share of ``mixed`` and fresh ``decode``
records whose rows took a token from an unfetched block is printed too, with
the trains' lengths. One JSON line a file. Stays off JAX.
"""
import json
import statistics
import sys

CLASSES = ("mixed_after_mixed", "first_mixed_after_drain", "fresh_decode_after_mixed", "rest")


def split(doc: dict) -> dict:
    lo, seconds = doc.get("epoch_open"), doc.get("seconds", 0)
    recs = [t for t in doc["entries"] if lo is None or lo <= t.get("ts", 0) <= lo + seconds]
    recs.sort(key=lambda t: t.get("submit_ts") or t.get("start_ts") or t.get("ts", 0))
    ms = dict.fromkeys(CLASSES, 0.0)
    n = dict.fromkeys(CLASSES, 0)
    stepped = {"device": 0, "host": 0}
    trains: list[int] = []
    prev = None
    for t in recs:
        fresh = t["kind"] == "decode" and t.get("h2d_arrays") == 1
        if t["kind"] == "mixed":
            cls = "mixed_after_mixed" if prev == "mixed" else "first_mixed_after_drain"
            if prev == "mixed":
                trains[-1] += 1
            else:
                trains.append(1)
        elif fresh and prev == "mixed":
            cls = "fresh_decode_after_mixed"
        else:
            cls = "rest"
        if t.get("starved_ms", 0) > 0:
            ms[cls] += t["starved_ms"]
            n[cls] += 1
        if (t["kind"] == "mixed" or fresh) and t.get("tokens_from") in stepped:
            stepped[t["tokens_from"]] += 1
        prev = t["kind"]
    total = sum(ms.values())
    out = {
        "dir": doc.get("dir"), "records": len(recs),
        "mixed": sum(t["kind"] == "mixed" for t in recs),
        "fresh_decode": sum(t["kind"] == "decode" and t.get("h2d_arrays") == 1 for t in recs),
        "trains": len(trains), "train_len_mean": round(statistics.fmean(trains), 2) if trains else None,
        "starved_ms": round(total, 1),
        "starved_share_of_window": round(total / 10 / seconds, 3) if seconds else None,
        "by_class_ms": {k: round(v, 1) for k, v in ms.items()},
        "by_class_share": {k: round(v / total, 3) if total else 0.0 for k, v in ms.items()},
        "starved_records": n,
    }
    if sum(stepped.values()):
        out["tokens_from"] = stepped
        out["tokens_from_device_share"] = round(stepped["device"] / sum(stepped.values()), 3)
    # What each admission waited out before its first step (ISSUE 48): the
    # decode steps dispatched and not yet fetched when it began, and the drain.
    preps = [t["prep"] for t in recs if t["kind"] == "mixed" and "drained_steps" in (t.get("prep") or {})]
    if preps:
        steps = sorted(p["drained_steps"] for p in preps)
        out["drained_steps"] = {
            "admissions": len(steps), "p50": steps[len(steps) // 2], "p90": steps[len(steps) * 9 // 10],
            "max": steps[-1], "mean": round(statistics.fmean(steps), 2),
            "count_by_steps": {str(k): steps.count(k) for k in sorted(set(steps))},
            "drain_ms_p50": round(statistics.median(p["drain_ms"] for p in preps), 2),
        }
    if doc.get("scheduler"):
        out["chunks_in_flight_max"] = doc["scheduler"].get("chunks_in_flight_max")
    batches = [(t["batch"], t["steps"]) for t in recs if t["kind"] == "decode" and t.get("steps")]
    if batches:
        out["decode_batch_mean"] = round(sum(b * s for b, s in batches) / sum(s for _, s in batches), 3)
    return out


if __name__ == "__main__":
    for path in sys.argv[1:]:
        with open(path) as f:
            print(json.dumps(split(json.load(f))))
