"""From a profiler trace (``.xplane.pb``) to the numbers the metric readers use.

Runs as a child of ``run.py`` (``python bench/trace_reduce.py <dir> <classes.json> <vocab>``)
with ``JAX_PLATFORMS=cpu``: it needs ``jax.profiler.ProfileData`` to read the
file and must not reach for the chip. The arithmetic (``reduce_events``) takes
plain tuples, so the tests drive it with a hand-built trace and no JAX.

What it computes, per device plane and then averaged over the devices used:
  busy_s     union of the intervals in which an operation ran
  window_s   the traced window: first event start to last event end on the device
             planes. Host threads go on for seconds after the device capture has
             ended (stop_trace gathers and writes), so their span is not it; the
             device span leaves out only idle time at the two edges, at most one
             step in a few seconds
  class_s    self time of operations by class (trace_classes.json); an
             operation's self time is its duration minus what its children on
             the same line cover, so a ``while`` and its body are not both counted
  modules    per program (XLA module) the count and the median duration
  top_ops    the operations with most self time
  gaps       the intervals with no operation running, and for each the
             innermost function of the program (host planes, Python tracer) that
             was running at its middle: what the host was doing, on the trace's
             own clock
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from statistics import median

Event = tuple[str, float, float]  # name, start_s, duration_s


def union_s(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals: list[tuple[float, float]], lo: float, hi: float,
            top: int = 10) -> list[tuple[float, float]]:
    """The longest (start, length) stretches of [lo, hi] no interval covers."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if s > edge:
            out.append((edge, s - edge))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi - edge))
    return sorted(out, key=lambda g: -g[1])[:top]


def self_times(events: list[Event]) -> list[tuple[str, float]]:
    """(name, self seconds) per event of one line: duration minus the union of
    the events nested inside it."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out: list[list] = []
    stack: list[tuple[float, int]] = []  # (end, index into out)
    for name, start, dur in order:
        end = start + dur
        while stack and start >= stack[-1][0] - 1e-12:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(dur, stack[-1][0] - start)
        out.append([name, dur])
        stack.append((end, len(out) - 1))
    return [(n, max(t, 0.0)) for n, t in out]


def classify(name: str, classes: list[tuple[str, re.Pattern]]) -> str:
    for cls, pat in classes:
        if pat.search(name):
            return cls
    return "other"


def compile_classes(spec: dict) -> list[tuple[str, re.Pattern]]:
    """The classes in order; ``<vocab>`` in a pattern stands for the served
    configuration's vocabulary size (spec["vocab"], set by the caller)."""
    vocab = str(spec.get("vocab", "0"))
    return [(c["class"], re.compile(c["regex"].replace("<vocab>", vocab))) for c in spec["classes"]]


def attribute(gaps: list[tuple[float, float]], host_events: list[Event], floor_s: float) -> dict[str, float]:
    """Idle seconds by what the host was doing: for each gap of at least
    ``floor_s``, the latest-started host event that spans its middle (the
    innermost frame of the program's own functions). Shorter gaps are summed
    under one name: they are the device's own pauses between operations."""
    import bisect

    evs = sorted(host_events, key=lambda ev: ev[1])
    starts = [ev[1] for ev in evs]
    sums: dict[str, float] = {}
    for start, length in gaps:
        if length < floor_s:
            what = f"between operations (< {floor_s * 1e6:.0f} us each)"
        else:
            mid = start + length / 2
            what = "no function of the program running"
            i = bisect.bisect_right(starts, mid) - 1
            for j in range(i, max(i - 400, -1), -1):
                name, s, d = evs[j]
                if s + d >= mid:
                    what = name
                    break
        sums[what] = sums.get(what, 0.0) + length
    return sums


def reduce_events(op_events: dict[str, list[Event]], module_events: dict[str, list[Event]],
                  window: tuple[float, float], spec: dict,
                  host_events: list[Event] | None = None) -> dict:
    """``op_events`` / ``module_events``: device plane name -> events of its
    operation line / its program line. ``window``: (start, end) seconds.
    ``host_events``: the program's own functions on the host threads."""
    classes = compile_classes(spec)
    lo, hi = window
    devices = sorted(op_events)
    busy, class_s, op_self = [], {}, {}
    all_gaps: list[tuple[float, float]] = []
    for dev in devices:
        evs = op_events[dev]
        spans = [(s, s + d) for _, s, d in evs]
        busy.append(union_s(spans))
        for name, t in self_times(evs):
            cls = classify(name, classes)
            class_s[cls] = class_s.get(cls, 0.0) + t
            op_self[name] = op_self.get(name, 0.0) + t
        if dev == devices[0]:
            all_gaps = gaps_of(spans, lo, hi, top=1 << 30)
    n = max(len(devices), 1)
    modules = {}
    for dev in devices[:1]:
        by_name: dict[str, list[float]] = {}
        for name, _, d in module_events.get(dev, []):
            by_name.setdefault(name, []).append(d)
        modules = {k: {"count": len(v), "median_s": median(v), "total_s": sum(v)}
                   for k, v in by_name.items()}
    out = {
        "devices": devices,
        "window": [lo, hi],
        "window_s": hi - lo,
        "busy_s": sum(busy) / n,
        "class_s": {k: v / n for k, v in class_s.items()},
        "modules": modules,
        "top_ops": sorted(((k, v / n) for k, v in op_self.items()), key=lambda kv: -kv[1])[:60],
        "gaps": [(s - lo, length) for s, length in all_gaps[:50]],
        "idle_by_host": sorted(attribute(all_gaps, host_events or [], float(spec.get("gap_floor_s", 5e-5))).items(),
                               key=lambda kv: -kv[1])[:10],
    }
    # Device time of a program: the median duration of the module the pattern
    # names (the one that ran most often, should two match).
    for key, pattern in (spec.get("modules") or {}).items():
        pat = re.compile(pattern)
        hits = [v for k, v in modules.items() if pat.search(k)]
        if hits:
            out[f"{key}_s"] = max(hits, key=lambda v: v["count"])["median_s"]
    return out


# ---------------------------------------------------------------------------
# Reading the file (needs jax.profiler.ProfileData)
# ---------------------------------------------------------------------------
def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return files[-1] if files else None


def read_xplane(path: str, spec: dict):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    dev_pat = re.compile(spec["device_plane"])
    op_line = re.compile(spec["op_line"])
    mod_line = re.compile(spec["module_line"])
    host_pat = re.compile(spec["host_plane"])
    span_pat = re.compile(spec["host_spans"])
    ops: dict[str, list[Event]] = {}
    mods: dict[str, list[Event]] = {}
    host: list[Event] = []
    host_names: dict[str, list] = {}
    lo, hi = float("inf"), float("-inf")
    layout = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in line.events]
            lines.append({"line": line.name, "events": len(evs)})
            if not evs:
                continue
            if dev_pat.search(plane.name):
                lo = min(lo, min(s for _, s, _ in evs))
                hi = max(hi, max(s + d for _, s, d in evs))
                if op_line.search(line.name):
                    ops.setdefault(plane.name, []).extend(evs)
                elif mod_line.search(line.name):
                    mods.setdefault(plane.name, []).extend(evs)
            elif host_pat.search(plane.name):
                for ev in evs:
                    if span_pat.search(ev[0]):
                        host.append(ev)
                        slot = host_names.setdefault(ev[0], [0, 0.0])
                        slot[0] += 1
                        slot[1] += ev[2]
        layout.append({"plane": plane.name, "lines": lines})
    names = sorted(host_names.items(), key=lambda kv: -kv[1][1])[:80]
    return ops, mods, (lo, hi), layout, host, names


def main(argv: list[str]) -> int:
    trace_dir, classes_path = argv[1], argv[2]
    with open(classes_path) as f:
        spec = json.load(f)
    if len(argv) > 3:
        spec["vocab"] = int(argv[3])
    path = find_xplane(trace_dir)
    if path is None:
        print(json.dumps({"error": f"no .xplane.pb under {trace_dir}"}))
        return 1
    ops, mods, window, layout, host, host_names = read_xplane(path, spec)
    if not ops:
        print(json.dumps({"error": "no device operation in the trace", "layout": layout}))
        return 1
    out = reduce_events(ops, mods, window, spec, host)
    out["host_names"] = host_names
    out["file"] = path
    out["file_bytes"] = os.path.getsize(path)
    out["layout"] = layout
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
