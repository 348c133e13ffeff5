#!/usr/bin/env python3
"""What the program's own annotations look like in a profiler trace (PR 24).

    JAX_PLATFORMS=cpu python3 bench/tools/span_trace.py <dir or .xplane.pb>

Prints one JSON object: the ``clock.sync`` event (its ``epoch_ns`` stat, where it
lies on the profiler's clock, and the offset between the two: epoch of the
profiler's zero), each host line that holds annotations of the scheduler thread
(``sched.*``, ``engine.*``, ``structured.*``) with count and total milliseconds a
name, how many events the Python tracer left on those lines (``$file.py:line
fn``), and the file's size. Run by hand, like the rest of bench/tools; needs
``jax.profiler.ProfileData`` and must not reach for the chip.
"""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from trace_reduce import find_xplane  # noqa: E402

ANNOTATION = re.compile(r"^(sched|engine|structured)\.[\w.]+$")


def main(argv: list[str]) -> int:
    from jax.profiler import ProfileData

    path = argv[1] if argv[1].endswith(".pb") else find_xplane(argv[1])
    if path is None:
        print(json.dumps({"error": f"no .xplane.pb under {argv[1]}"}))
        return 1
    out: dict = {"file": path, "file_bytes": os.path.getsize(path), "clock_sync": None, "lines": []}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names: dict[str, list] = {}
            python_frames = 0
            for ev in line.events:
                if ev.name == "clock.sync":
                    epoch_ns = int(dict(ev.stats).get("epoch_ns", 0))
                    out["clock_sync"] = {"plane": plane.name, "line": line.name, "epoch_ns": epoch_ns,
                                         "trace_ns": ev.start_ns, "zero_epoch_ns": epoch_ns - int(ev.start_ns)}
                elif ANNOTATION.match(ev.name):
                    slot = names.setdefault(ev.name, [0, 0.0])
                    slot[0] += 1
                    slot[1] += ev.duration_ns / 1e6
                elif ev.name.startswith("$"):
                    python_frames += 1
            if names:
                out["lines"].append({"plane": plane.name, "line": line.name, "python_frames": python_frames,
                                     "annotations": {k: {"count": c, "total_ms": round(ms, 3)}
                                                     for k, (c, ms) in sorted(names.items())}})
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
