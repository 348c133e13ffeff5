"""The operations under one named scope of a capture, each with its device time.

    JAX_PLATFORMS=cpu python3 hack/scope_ops.py <file.xplane.pb> ssm.conv [--module mixed_step] [--most 24]

`bench/scope_times.py` sums a capture's device time by scope; this prints what
a scope's sum is made of (ISSUE 53's step 0: which of `ops/ssm.conv_ragged`'s
operations hold its 392 us a layer). An operation belongs to a scope as there:
its instruction's `op_name` in the capture's own HLO, the innermost component
the vocabulary names. Self seconds, us a program of the module, the sum first;
the same in `<capture>.<scope>.<module>.ops.json`, a file a scope and module.

Not part of the benchmark; reads a capture, never the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "bench"))


def scope_ops(path: str, scope: str, module: str = "mixed_step") -> dict:
    """{"programs", "us_a_program", "ops": [[event name, us a program, events a program]]} of
    ``scope`` in the step programs of ``module`` (a key of ``trace_classes.json``'s ``modules``)."""
    from jax.profiler import ProfileData

    import scope_times
    from trace_reduce import self_times

    spec = json.load(open(os.path.join(HERE, "bench", "trace_classes.json")))
    dev, op_line, mod_line = (re.compile(spec[k]) for k in ("device_plane", "op_line", "module_line"))
    wanted = re.compile(spec["modules"][module])
    with open(path, "rb") as f:
        raw = f.read()
    hlo = scope_times.hlo_scopes(raw)
    by_base = {name.split("(", 1)[0]: table for name, table in hlo.items()}
    scope_class = scope_times.load_vocabulary()["scope_class"]
    ops: dict[str, list[float]] = {}
    programs = 0
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not dev.search(plane.name):
            continue
        events, mods = [], []
        for line in plane.lines:
            if op_line.search(line.name):
                events += [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in line.events]
            elif mod_line.search(line.name):
                mods += [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name) for ev in line.events
                         if wanted.search(ev.name)]
        programs += len(mods)
        events.sort(key=lambda ev: (ev[1], -ev[2]))
        mods.sort()
        selfs = self_times([(i, s, d) for i, (_, s, d) in enumerate(events)])
        j = -1
        for (_, t), (name, start, _) in zip(selfs, events):
            while j < len(mods) - 1 and mods[j + 1][0] <= start:  # the events come by start
                j += 1
            inside = mods[j][2] if j >= 0 and start < mods[j][1] else None
            if inside is None:
                continue
            table = hlo.get(inside) or by_base.get(inside.split("(", 1)[0]) or {}
            m = scope_times.LEADING_NAME.match(name)
            if scope_times.scope_of(table.get(m.group(1) if m else "", ""), scope_class) == scope:
                got = ops.setdefault(name, [0.0, 0])
                got[0] += t
                got[1] += 1
    n = max(programs, 1)
    rows = sorted(([name, 1e6 * s / n, count / n] for name, (s, count) in ops.items()), key=lambda r: -r[1])
    return {"file": path, "scope": scope, "module": module, "programs": programs,
            "us_a_program": sum(r[1] for r in rows), "ops": rows}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("scope")
    ap.add_argument("--module", default="mixed_step")
    ap.add_argument("--most", type=int, default=24)
    args = ap.parse_args()
    got = scope_ops(args.xplane, args.scope, args.module)
    print(f"{got['scope']} in {got['programs']} x {got['module']}: {got['us_a_program']:.1f} us a program")
    for name, us, count in got["ops"][:args.most]:
        print(f"  {us:9.1f} us  x{count:5.1f}  {name[:150]}")
    with open(re.sub(r"\.xplane\.pb$", "", args.xplane) + f".{args.scope}.{args.module}.ops.json", "w") as f:
        json.dump(got, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
