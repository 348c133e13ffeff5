"""Bytes the expert layer of ONE chip must read in one decode step, and where
its device time is found in a trace: what the ``moe_*`` readers share.

An expert configuration's decode step reads, in every layer, the three
matrices of each DISTINCT expert its rows chose, once, at the served weight
type: ``experts_read`` x 3 x hidden x expert width x layers. How many were
chosen only the program can count (``experts_read`` on its ``decode`` timeline
records; ``layers/decode_hbm_roofline_share.py`` says where the count stands).
The router, the scales and the norms are left out, which makes a share a lower
bound. Sizes come from the configuration's file through ``ops_bytes``, never
from the program.

The expert kernels' device time is read off the trace file itself, by a child
(``python bench/moe_bytes.py <file.xplane.pb>``, ``JAX_PLATFORMS=cpu``: it needs
``jax.profiler.ProfileData`` and must not reach for the chip; ~3 s for a 40 MB
file): every ``moe_*_gmm`` event of the operation line, and of those the ones
that ran inside a decode program, told by the program (XLA module) event that
encloses them, never by a shape or a tile of the kernel's. ``trace_reduce``'s
``top_ops`` are no source for it: the 60 largest, summed over programs.
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

from ops_bytes import DTYPE_BYTES, expert_params, sizes
from stats import dispatched_in_capture

BENCH = Path(__file__).resolve().parent
# The expert layer's two kernels keep the ``name=`` of their ``pallas_call``
# (ops/moe.py): an event on the XLA Ops line is the whole HLO instruction,
# ``%moe_gate_up_gmm.3 = bf16[288,768]{...} custom-call(...)``.
MOE_OP = re.compile(r"^%?moe_(?:gate_up|down)_gmm\b")

Event = tuple[str, float, float]  # name, start_s, duration_s


def expert_bytes_per_step(config: dict, experts_read: float) -> float:
    """Bytes of expert weights one chip reads in a decode step."""
    s = sizes(config)
    wbytes = DTYPE_BYTES[config["deployment_sizes"]["weight_dtype"]]
    return s["L"] * experts_read * expert_params(config) * wbytes / s["tp"]


def attribute(op_events: list[Event], module_events: list[Event], decode_module: re.Pattern) -> dict:
    """One device's lines: seconds in the expert kernels (custom calls: leaves,
    so a duration is a self time), the part of it inside a program whose name
    ``decode_module`` matches (an operation belongs to the program event that
    encloses its start), and how many such programs the line holds."""
    mods = sorted((start, start + dur, name) for name, start, dur in module_events)
    starts = [m[0] for m in mods]
    out = {"all_s": 0.0, "decode_s": 0.0, "moe_events": 0,
           "decode_programs": sum(1 for m in mods if decode_module.search(m[2]))}
    for name, start, dur in op_events:
        if not MOE_OP.match(name):
            continue
        out["all_s"] += dur
        out["moe_events"] += 1
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < mods[i][1] and decode_module.search(mods[i][2]):
            out["decode_s"] += dur
    return out


def read_file(path: str) -> dict:
    """``attribute`` over the device planes of an ``.xplane.pb``, averaged over
    the devices (as ``trace_reduce`` averages), with ``trace_classes.json``'s
    names for the planes, the lines and the decode program."""
    from jax.profiler import ProfileData

    spec = json.loads((BENCH / "trace_classes.json").read_text())
    dev, op_line, mod_line = (re.compile(spec[k]) for k in ("device_plane", "op_line", "module_line"))
    decode_module = re.compile(spec["modules"]["decode_chunk"])
    per_device = []
    for plane in ProfileData.from_file(path).planes:
        if not dev.search(plane.name):
            continue
        lines = {"op": [], "mod": []}
        for line in plane.lines:
            key = "op" if op_line.search(line.name) else "mod" if mod_line.search(line.name) else None
            if key:
                lines[key] += [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in line.events]
        if lines["op"]:
            per_device.append(attribute(lines["op"], lines["mod"], decode_module))
    n = max(len(per_device), 1)
    return {k: sum(d[k] for d in per_device) / n for k in ("all_s", "decode_s", "moe_events", "decode_programs")}


@functools.lru_cache(maxsize=4)
def _read_in_child(path: str) -> dict:
    done = subprocess.run([sys.executable, str(Path(__file__).resolve()), path], capture_output=True, text=True,
                          timeout=240, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if done.returncode != 0:
        raise RuntimeError(f"bench/moe_bytes.py could not read {path}: {done.stderr[-2000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


def expert_kernel_times(trace: dict) -> dict | None:
    """``read_file`` of the trace the run reduced (its ``file``), once a run;
    None where the trace holds no expert kernel: a program without the layer.
    A hand-built trace brings its own ``expert_kernels``."""
    times = trace.get("expert_kernels") or (_read_in_child(trace["file"]) if trace.get("file") else None)
    return times if times and times["all_s"] > 0 else None


def mean_experts_read(records) -> float | None:
    """Mean ``experts_read`` of the ``decode`` records among ``records``,
    weighted by their steps; None while none carries the field."""
    recs = [t for t in records or []
            if t.get("kind") == "decode" and t.get("steps") and t.get("experts_read") is not None]
    steps = sum(t["steps"] for t in recs)
    return sum(t["experts_read"] * t["steps"] for t in recs) / steps if steps else None


def experts_read_in_capture(sources: dict) -> float | None:
    """``mean_experts_read`` over the records dispatched inside the capture."""
    return mean_experts_read(dispatched_in_capture(sources))


if __name__ == "__main__":
    print(json.dumps(read_file(sys.argv[1])))
