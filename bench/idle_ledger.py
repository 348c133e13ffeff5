"""Why the device waited, by the program's own account: what the five readers
of the idle ledger share (``starved_slot_share``, ``starved_delivery_share``,
``starved_unspanned_share``, ``idle_unspanned_share``, ``fetch_return_ms``).

Two sources, both the program's. (1) ``starved_by`` on a timeline record: the
record's ``starved_ms`` by the cause the scheduler thread was in (the last word
of the span that was open, ``loop`` where none was: docs/observability.md has
the closed list). Summed over the window's records it is the whole window's
account, from an untraced timeline as well as a traced one. (2) The same spans
as annotations in a profiler capture, on one clock with the device's operation
and program events: device-idle seconds by the innermost annotation the
scheduler thread had open, and the fetch's way back (the step program's end on
the device to the end of the ``fetch_copy`` annotation on the host). The file's
two clocks are NOT one: the device plane's is first placed on the host's by
causality (``device_clock_shift``).

The trace file is read by a child (``python bench/idle_ledger.py
<file.xplane.pb>``, ``JAX_PLATFORMS=cpu``: it needs ``jax.profiler.ProfileData``
and must not reach for the chip), once a run, as ``moe_bytes`` reads it; what it
counted is left beside the trace as ``<file>.idle_by_span.json``.
The arithmetic (``account``) takes plain tuples, so the tests drive it with a
hand-made event list. By hand, ``python bench/idle_ledger.py <timeline.json>``
prints the by-cause table of a saved ``/debug/timeline`` document.

A program whose records carry no ``starved_by`` (every tree before ISSUE 38)
gets None from every function here that a reader returns.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import re
import subprocess
import sys
from pathlib import Path

from stats import median

BENCH = Path(__file__).resolve().parent
ANNOTATION = re.compile(r"^(sched|engine|structured)\.[\w.]+$")
FETCH = re.compile(r"^engine\.\w+\.fetch$")
FETCH_COPY = re.compile(r"^engine\.\w+\.fetch_copy$")
# The program's closed list of causes (docs/observability.md), in the order a
# host-stepped step passes them: the last word of each span, the lock's wait,
# and ``loop`` for what lies under no span.
CAUSES = ("wake", "rows", "prep", "register_slot", "drain", "build", "lock_wait", "pages", "assemble",
          "upload_dispatch", "deliver", "fetch", "fetch_copy", "emit", "release", "release_slot", "record", "loop")
# The causes three readers sum (ROADMAP S3's levers).
SLOT = ("register_slot", "release_slot", "release")
DELIVERY = ("emit", "deliver")
UNSPANNED = ("loop", "wake", "record")

Event = tuple[str, float, float]  # name, start_s, duration_s


# ---------------------------------------------------------------------------
# The records' side
# ---------------------------------------------------------------------------
def cause_ms(records) -> dict[str, float] | None:
    """Milliseconds starved by cause, summed over ``records``; None while none
    of them carries ``starved_by``."""
    ledgers = [t["starved_by"] for t in records or [] if t.get("starved_by")]
    if not ledgers:
        return None
    out: dict[str, float] = {}
    for by in ledgers:
        for cause, ms in by.items():
            out[cause] = out.get(cause, 0.0) + ms
    return out


def starved_share(sources: dict, causes: tuple[str, ...]) -> float | None:
    """Percent of the window's seconds the device was starved under ``causes``."""
    by = cause_ms(sources.get("timeline"))
    seconds = (sources.get("window") or {}).get("seconds")
    if by is None or not seconds:
        return None
    return 100.0 * sum(by.get(c, 0.0) for c in causes) / 1e3 / seconds


def table(records) -> dict:
    """The whole-window table by cause of a list of records (PERF.md section
    5): per kind of dispatch that starved (a mixed step, a fresh decode
    submit), how many, the median and the total of ``starved_ms``, and the
    total and the per-record median of each cause."""
    out = {}
    for kind in ("mixed", "decode", "prefill"):
        recs = [t for t in records if t.get("kind") == kind and t.get("starved_by")]
        if not recs:
            continue
        causes = sorted({c for t in recs for c in t["starved_by"]})
        out[kind] = {
            "records": len(recs), "starved_ms_median": median([t["starved_ms"] for t in recs]),
            "starved_ms_total": sum(t["starved_ms"] for t in recs),
            "by_cause_total_ms": {c: sum(t["starved_by"].get(c, 0.0) for t in recs) for c in causes},
            "by_cause_median_ms": {c: median([t["starved_by"].get(c, 0.0) for t in recs]) for c in causes}}
    return out


# ---------------------------------------------------------------------------
# The capture's side
# ---------------------------------------------------------------------------
def innermost(annotations: list[Event]) -> list[tuple[float, float, str]]:
    """(start, end, name) stretches of one thread's line, each under the
    innermost annotation open in it; stretches under none are left out.
    Annotations of one thread nest."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name)
    t = 0.0

    def put(a: float, b: float, name: str) -> None:
        if b > a:
            out.append((a, b, name))

    for name, start, dur in sorted(annotations, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= start:
            end, top = stack.pop()
            put(t, end, top)
            t = max(t, end)
        if stack:
            put(t, start, stack[-1][1])
        t = start
        stack.append((start + dur, name))
    while stack:
        end, top = stack.pop()
        put(t, end, top)
        t = max(t, end)
    return out


def idle_by_span(gaps: list[tuple[float, float]], stretches: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds of the (start, end) ``gaps`` by the annotation of the stretch
    they fall in, cut at the stretches' edges; what no stretch covers, and what
    lies under ``sched.loop`` alone, is ``sched.loop``."""
    starts = [s[0] for s in stretches]
    out: dict[str, float] = {}
    for lo, hi in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(stretches) and stretches[i][0] < hi:
            a, b, name = stretches[i]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            i += 1
        if hi - lo > covered:
            out["sched.loop"] = out.get("sched.loop", 0.0) + (hi - lo) - covered
    return out


def _step_pattern(spec: dict) -> re.Pattern:
    """The step programs of ``trace_classes.json`` (``modules``), as one pattern."""
    return re.compile("|".join(f"(?:{p})" for p in (spec.get("modules") or {}).values()) or r"^\b$")


def _fetched(annotations: list[Event], module_events: list[Event], step_module: re.Pattern):
    """(program start, program end, fetch end) of every step program that ended
    while the scheduler thread sat in a fetch annotation: the host waited for it."""
    fetches = sorted((s, s + d) for n, s, d in annotations if FETCH.match(n))
    starts = [f[0] for f in fetches]
    for name, start, dur in module_events:
        if step_module.search(name):
            i = bisect.bisect_right(starts, start + dur) - 1
            if i >= 0 and fetches[i][1] >= start + dur:
                yield start, start + dur, fetches[i][1]


def device_clock_shift(annotations: list[Event], module_events: list[Event], spec: dict) -> dict:
    """Seconds to ADD to the device plane's times to put them on the host
    plane's clock, estimated from the capture itself by causality. The two
    planes of one file need not agree: on the chip the device plane read
    0.3-2.9 ms EARLY (programs "started" 0.5-1.5 ms before the call that
    dispatched them had begun; ``PERF.md`` section 6, PR 38). Hard bounds: a
    program cannot start before the ``upload_dispatch`` span that dispatched it
    (``lower``), nor can a fetch return before its program has ended
    (``upper``). Between them the estimate is the median, over the mixed steps,
    of span end - program start: the jitted call is the last statement of
    ``engine.mixed.upload_dispatch`` and a mixed step is dispatched onto an
    empty device, so the program starts within a launch's latency of the span's
    end (some 0.1-0.3 ms either way: the estimate's error, and so that of
    ``fetch_return_ms``). No fetched step: no estimate, shift 0."""
    uppers = [back - end for _, end, back in _fetched(annotations, module_events, _step_pattern(spec))]
    if not uppers:
        return {"shift_s": 0.0, "lower_s": None, "upper_s": None, "anchors": 0}
    upper = min(uppers)
    mixed = re.compile((spec.get("modules") or {}).get("mixed_step", r"^\b$"))
    calls = sorted((s, s + d) for n, s, d in annotations if n == "engine.mixed.upload_dispatch")
    ends = [c[1] for c in calls]
    lowers, anchors = [], []
    for name, start, _ in module_events:
        if not calls or not mixed.search(name):
            continue
        i = min(bisect.bisect_left(ends, start), len(calls) - 1)
        i = min((j for j in (i - 1, i) if j >= 0), key=lambda j: abs(ends[j] - start))
        if abs(ends[i] - start) < 0.010:   # its own call: mixed steps lie tens of ms apart
            lowers.append(calls[i][0] - start)
            anchors.append(ends[i] - start)
    lower = max(lowers, default=None)
    shift = upper if not anchors else min(max(median(anchors), lower), upper)
    return {"shift_s": shift, "lower_s": lower, "upper_s": upper, "anchors": len(anchors)}


def fetch_returns(annotations: list[Event], module_events: list[Event], step_module: re.Pattern) -> list[dict]:
    """One entry a host-stepped step of the capture: a step program (a module
    event ``step_module`` matches) that ended while the scheduler thread was
    inside a fetch annotation, with no program queued behind it (none starts
    before the host has its result: a chained chunk has one, and is left out).
    ``return_ms``: the program's end to the end of the ``fetch_copy`` that
    follows the fetch; ``wake_ms`` to the end of the fetch itself
    (``block_until_ready`` returned); ``copy_ms`` the rest. The events are on
    one clock already (``account`` shifts the device's)."""
    copies = sorted((s, s + d) for n, s, d in annotations if FETCH_COPY.match(n))
    copy_starts = [c[0] for c in copies]
    mod_starts = sorted(s for _, s, _ in module_events)
    out = []
    for start, end, woke in sorted(_fetched(annotations, module_events, step_module)):
        j = bisect.bisect_left(copy_starts, woke)
        if j == len(copies):
            continue
        back = copies[j][1]
        nxt = bisect.bisect_right(mod_starts, start)
        if nxt < len(mod_starts) and mod_starts[nxt] < back:
            continue  # something was queued behind it: not host-stepped
        out.append({"return_ms": (back - end) * 1e3, "wake_ms": (woke - end) * 1e3, "copy_ms": (back - woke) * 1e3})
    return out


def _by_cause(by_annotation: dict[str, float]) -> dict[str, float]:
    out: dict[str, float] = {}
    for name, s in by_annotation.items():
        cause = name.rpartition(".")[2]
        out[cause] = out.get(cause, 0.0) + s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def account(op_events: list[Event], module_events: list[Event], annotations: list[Event], spec: dict) -> dict:
    """One device's operation and program lines against the scheduler thread's
    annotations, the device's times first put on the host's clock
    (``device_clock_shift``). Idle is what no operation covers between the
    device's first event and its last; gaps under ``gap_floor_s`` are the
    device's own pauses between operations and are kept apart, as
    ``trace_reduce`` keeps them. ``idle_by_span_unshifted`` is the same account
    on the file's times as they stand: what ``breakdown.idle_gaps`` (the Python
    tracer's frames) can be compared with."""
    floor = float(spec.get("gap_floor_s", 5e-5))
    clock = device_clock_shift(annotations, module_events, spec)
    shift = clock["shift_s"]
    spans = sorted((s, s + d) for _, s, d in op_events)
    gaps, edge = [], spans[0][0] if spans else 0.0
    for s, e in spans:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    long_gaps = [g for g in gaps if g[1] - g[0] >= floor]
    stretches = innermost(annotations)
    by_annotation = idle_by_span([(a + shift, b + shift) for a, b in long_gaps], stretches)
    by_cause = _by_cause(by_annotation)
    returns = fetch_returns(annotations, [(n, s + shift, d) for n, s, d in module_events], _step_pattern(spec))
    idle = sum(e - s for s, e in long_gaps)
    return {
        "gap_floor_s": floor, "idle_s": idle, "between_operations_s": sum(e - s for s, e in gaps) - idle,
        "device_clock_shift_ms": shift * 1e3,
        "device_clock_shift_bounds_ms": [None if v is None else v * 1e3 for v in (clock["lower_s"], clock["upper_s"])],
        "device_clock_anchors": clock["anchors"],
        "unspanned_s": by_cause.get("loop", 0.0), "idle_by_span": by_cause,
        "idle_by_annotation": dict(sorted(by_annotation.items(), key=lambda kv: -kv[1])),
        "idle_by_span_unshifted": _by_cause(idle_by_span(long_gaps, stretches)),
        "annotations": len(annotations), "host_stepped_steps": len(returns),
        "fetch_return_ms": median([r["return_ms"] for r in returns]),
        "fetch_wake_ms": median([r["wake_ms"] for r in returns]),
        "fetch_copy_ms": median([r["copy_ms"] for r in returns]),
        "fetch_return_max_ms": max((r["return_ms"] for r in returns), default=None),
    }


def read_file(path: str) -> dict:
    """``account`` over the first device plane of an ``.xplane.pb`` (where
    ``trace_reduce`` takes its gaps) and the host line that holds the most
    annotations of the program: the scheduler thread's. Planes and lines by
    ``trace_classes.json``'s names."""
    from jax.profiler import ProfileData

    spec = json.loads((BENCH / "trace_classes.json").read_text())
    dev, op_line, mod_line, host = (re.compile(spec[k]) for k in ("device_plane", "op_line", "module_line", "host_plane"))
    ops: dict[str, list[Event]] = {}
    mods: dict[str, list[Event]] = {}
    threads: list[list[Event]] = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if dev.search(plane.name):
                key = ops if op_line.search(line.name) else mods if mod_line.search(line.name) else None
                if key is not None:
                    key.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in line.events)
            elif host.search(plane.name):
                evs = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in line.events
                       if ANNOTATION.match(ev.name)]
                if evs:
                    threads.append(evs)
    if not ops:
        return {"error": "no device operation in the trace"}
    first = sorted(ops)[0]
    thread = max(threads, key=len, default=[])
    out = account(ops[first], mods.get(first, []), thread, spec)
    out["device"] = first
    out["annotated_threads"] = len(threads)
    return out


@functools.lru_cache(maxsize=4)
def _read_in_child(path: str) -> dict:
    done = subprocess.run([sys.executable, str(Path(__file__).resolve()), path], capture_output=True, text=True,
                          timeout=240, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if done.returncode != 0:
        raise RuntimeError(f"bench/idle_ledger.py could not read {path}: {done.stderr[-2000:]}")
    found = json.loads(done.stdout.strip().splitlines()[-1])
    try:   # the run's record of what was counted: idle seconds by span, the way back
        Path(path + ".idle_by_span.json").write_text(json.dumps(found, indent=1))
    except OSError:
        pass
    return found


def capture_account(sources: dict) -> dict | None:
    """``read_file`` of the trace the run reduced (its ``file``), once a run;
    None without a trace, or on a program whose records carry no ``starved_by``
    (its capture has the older annotations, and an account of it would compare
    with nothing). A hand-made trace brings its ``idle_ledger_events``
    (``ops``, ``modules``, ``annotations``, ``spec``)."""
    trace = sources.get("trace")
    if not trace or cause_ms(sources.get("timeline")) is None:
        return None
    if trace.get("idle_ledger_events"):
        ev = trace["idle_ledger_events"]
        return account(ev["ops"], ev["modules"], ev["annotations"], ev.get("spec") or {})
    got = _read_in_child(trace["file"]) if trace.get("file") else None
    return got if got and not got.get("error") else None


if __name__ == "__main__":
    if sys.argv[1].endswith(".pb"):
        result = read_file(sys.argv[1])
    else:  # a saved /debug/timeline document; hack/timeline_run.py's says where the window lay
        doc = json.loads(Path(sys.argv[1]).read_text())
        lo, seconds = doc.get("epoch_open"), doc.get("seconds")
        result = table([t for t in doc["entries"] if lo is None or lo <= t.get("ts", 0) <= lo + seconds])
    print(json.dumps(result))
