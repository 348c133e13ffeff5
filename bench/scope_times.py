"""Device time by the program's own named scopes, for every family: what the
``scope_*_share`` readers share.

The program names its parts with ``jax.named_scope`` (``attention``,
``moe.combine``, ``ssm.scan``, ...: the closed list of ``scope_classes.json``,
which ``docs/observability.md`` repeats). An operation event of a profile
carries only its HLO text, device offset and duration, but the capture also
holds the optimised HLO of every program it ran: the ``.xplane.pb``'s
``/host:metadata`` plane has one event-metadata entry a module whose ``Hlo
Proto`` stat is the module's ``HloProto``, and every instruction there (a
fusion or a custom call by its root) keeps ``metadata.op_name``, a path like
``jit(_mixed_step_fn)/jit(main)/while/body/attention/moe.router/dot_general``.
So: event -> its leading ``%name`` -> the instruction of the module whose
``XLA Modules`` event encloses it -> ``op_name`` -> the INNERMOST component
that is a name of the vocabulary -> its class. An operation of a step program
(``trace_classes.json``'s ``modules`` patterns) under no known name is
``unscoped``; one of any other program is ``other_programs``. Seconds are SELF
times (``trace_reduce.self_times``: a loop or a conditional keeps what its
children do not cover), so the classes sum to the trace's ``busy_s`` as
``class_s`` does.

What it cannot see: a fusion that spans two scopes goes to ONE of them, the
compiler's choice (its root's; an output fusion's matrix product's: Granite's
shared MLP's last product holds the combine's weighted sum and the residual
add, 0.56 ms a step under ``moe.shared``); a scope INSIDE a Pallas kernel is
one event, the kernel's; an executable loaded from a compile cache that an
older tree filled carries that tree's names.

Files: ``scope_classes.json`` is the vocabulary, the one data file; this module
is the one reader; ``layers/scope_<class>_share.py`` are six one-line metrics
over ``class_share``. A traced run leaves ``<trace>.by_scope.json`` beside its
``.xplane.pb``: ``{"hlo_from", "busy_s", "reader_s", "modules": {"mixed_step" |
"decode_chunk" | "other_programs": {"programs", "events", "seconds", "class_s",
"scope_s", "unscoped_top"}}}``. Read once a run, in a child (``python
bench/scope_times.py <file.xplane.pb>``, ``JAX_PLATFORMS=cpu``: the events come
through ``jax.profiler.ProfileData``, which does not expose the metadata plane,
and the child must not reach for the chip).

The protobuf wire format is read here by hand (no ``tensorflow`` import: 13 s;
nothing to install). Field numbers, from the descriptors of
``tsl/profiler/protobuf/xplane_pb2`` and ``xla/service/hlo_pb2`` /
``xla/xla_data_pb2`` as installed beside TensorFlow 2.21:

    XSpace.planes = 1                      XPlane.name = 2
    XPlane.event_metadata = 4 (map: key 1, value 2)
    XPlane.stat_metadata = 5 (map: key 1, value 2)
    XEventMetadata.id = 1, .name = 2, .stats = 5
    XStatMetadata.id = 1, .name = 2
    XStat.metadata_id = 1, .bytes_value = 6
    HloProto.hlo_module = 1
    HloModuleProto.name = 1, .computations = 3
    HloComputationProto.instructions = 2
    HloInstructionProto.name = 1, .metadata = 7
    OpMetadata.op_name = 2
"""

from __future__ import annotations

import functools
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

from trace_reduce import self_times

BENCH = Path(__file__).resolve().parent
METADATA_PLANE = "/host:metadata"
HLO_STAT = re.compile(r"hlo.?proto", re.I)
STEP_MODULES = ("mixed_step", "decode_chunk")
LEADING_NAME = re.compile(r"^%?([\w.\-]+)")

Event = tuple[str, float, float]  # name, start_s, duration_s


# ---------------------------------------------------------------------------
# The wire format: varints and length-delimited fields, nothing else decoded
# ---------------------------------------------------------------------------
def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        c = buf[i]
        i += 1
        value |= (c & 0x7F) << shift
        if c < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: an int for a varint,
    a memoryview for a length-delimited field or a fixed 32 / 64."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire in (1, 2, 5):
            size, i = _varint(buf, i) if wire == 2 else (8 if wire == 1 else 4, i)
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not a protobuf message")
        yield key >> 3, wire, value


def _first(buf, number: int):
    return next((v for f, _, v in fields(buf) if f == number), None)


def _text(view) -> str:
    return bytes(view).decode("utf-8", errors="replace") if view is not None else ""


def instruction_scopes(hlo_proto) -> dict[str, str]:
    """{instruction name: op_name} over every computation of one ``HloProto``
    (names are unique in a module). An instruction without metadata maps to ''."""
    out: dict[str, str] = {}
    module = _first(hlo_proto, 1)
    if module is None:
        return out
    for f, wire, computation in fields(module):
        if f != 3 or wire != 2:
            continue
        for g, wire_g, instruction in fields(computation):
            if g != 2 or wire_g != 2:
                continue
            name, op_name = None, ""
            for h, _, v in fields(instruction):
                if h == 1:
                    name = _text(v)
                elif h == 7:
                    op_name = _text(_first(v, 2))
            if name:
                out[name] = op_name
    return out


def hlo_scopes(xplane) -> dict[str, dict[str, str]]:
    """{module as the capture names it, 'jit__mixed_step_fn(<id>)':
    {instruction: op_name}} from the ``/host:metadata`` plane of an
    ``.xplane.pb``'s bytes; {} where the capture holds no HLO."""
    out: dict[str, dict[str, str]] = {}
    for f, wire, plane in fields(xplane):
        if f != 1 or wire != 2 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        hlo_stats, modules = set(), []
        for g, wire_g, entry in fields(plane):
            if wire_g != 2 or g not in (4, 5):
                continue
            value = _first(entry, 2)
            if value is None:
                continue
            if g == 5:
                ident = _first(value, 1) or 0
                if HLO_STAT.search(_text(_first(value, 2))):
                    hlo_stats.add(ident)
            else:
                modules.append(value)
        for meta in modules:
            name = _text(_first(meta, 2))
            for g, wire_g, stat in fields(meta):
                if g != 5 or wire_g != 2:
                    continue
                blob = _first(stat, 6)
                if blob is not None and ((_first(stat, 1) or 0) in hlo_stats or not hlo_stats):
                    out[name] = instruction_scopes(blob)
    return out


# ---------------------------------------------------------------------------
# The vocabulary
# ---------------------------------------------------------------------------
def load_vocabulary(path: Path | None = None) -> dict:
    spec = json.loads((path or BENCH / "scope_classes.json").read_text())
    scope_class = {scope: cls for cls, scopes in spec["classes"].items() for scope in scopes}
    return {"scope_class": scope_class, "unscoped": spec["unscoped"], "other_programs": spec["other_programs"]}


def scope_of(op_name: str, scope_class: dict[str, str]) -> str | None:
    """The innermost component of ``op_name`` that the vocabulary names."""
    for part in reversed(op_name.split("/")):
        if part in scope_class:
            return part
    return None


# ---------------------------------------------------------------------------
# One device's lines
# ---------------------------------------------------------------------------
def _base(module: str) -> str:
    return module.split("(", 1)[0]


def attribute(op_events: list[Event], module_events: list[Event], hlo: dict[str, dict[str, str]],
              vocab: dict, module_patterns: dict[str, str]) -> dict:
    """Self seconds of one device's operation line by module (``mixed_step``,
    ``decode_chunk``, ``other_programs``), class and scope. An operation
    belongs to the program event that encloses its start; its instruction is
    looked up in that program's HLO (by the event's whole name, else by the
    name before its ``(<id>)``)."""
    scope_class, unscoped, other = vocab["scope_class"], vocab["unscoped"], vocab["other_programs"]
    patterns = [(key, re.compile(pat)) for key, pat in module_patterns.items()]
    by_base: dict[str, dict[str, str]] = {}
    for name, table in hlo.items():
        by_base.setdefault(_base(name), table)
    mods = sorted((start, start + dur, name) for name, start, dur in module_events)
    keyed: dict[str, tuple[str, dict[str, str] | None]] = {}  # module event name -> (key, its instructions)

    def keyed_module(name: str) -> tuple[str, dict[str, str] | None]:
        if name not in keyed:
            key = next((k for k, pat in patterns if pat.search(name)), other)
            keyed[name] = (key, hlo.get(name) or by_base.get(_base(name)))
        return keyed[name]

    out = {key: {"programs": 0, "events": 0, "seconds": 0.0, "class_s": {}, "scope_s": {}, "unscoped_ops": {}}
           for key in (*module_patterns, other)}
    for _, _, name in mods:
        out[keyed_module(name)[0]]["programs"] += 1
    memo: dict[tuple[str, str], tuple[str, str, str]] = {}  # (module event name, op event name) -> (module key, class, scope)
    events = sorted(op_events, key=lambda ev: (ev[1], -ev[2]))
    selfs = self_times([(i, s, d) for i, (_, s, d) in enumerate(events)])  # in the same order: the sort is stable
    j, last = -1, len(mods) - 1
    for (_, t), (name, start, _) in zip(selfs, events):
        while j < last and mods[j + 1][0] <= start:  # the events come by start: the enclosing program only moves on
            j += 1
        module = mods[j][2] if j >= 0 and start < mods[j][1] else ""
        got = memo.get((module, name))
        if got is None:
            key, table = keyed_module(module) if module else (other, None)
            if key == other:
                got = (key, other, other)
            else:
                m = LEADING_NAME.match(name)
                scope = scope_of((table or {}).get(m.group(1) if m else "", ""), scope_class)
                got = (key, scope_class[scope], scope) if scope else (key, unscoped, unscoped)
            memo[(module, name)] = got
        key, cls, scope = got
        slot = out[key]
        slot["events"] += 1
        slot["seconds"] += t
        slot["class_s"][cls] = slot["class_s"].get(cls, 0.0) + t
        slot["scope_s"][scope] = slot["scope_s"].get(scope, 0.0) + t
        if cls == unscoped:
            slot["unscoped_ops"][name] = slot["unscoped_ops"].get(name, 0.0) + t
    return out


def merge(per_device: list[dict]) -> dict:
    """The devices' accounts averaged (as ``trace_reduce`` averages), the five
    longest ``unscoped`` operations of each module kept by name."""
    n = max(len(per_device), 1)
    out: dict[str, dict] = {}
    for dev in per_device:
        for key, slot in dev.items():
            to = out.setdefault(key, {"programs": 0.0, "events": 0.0, "seconds": 0.0, "class_s": {}, "scope_s": {},
                                      "unscoped_ops": {}})
            for field in ("programs", "events", "seconds"):
                to[field] += slot[field] / n
            for field in ("class_s", "scope_s", "unscoped_ops"):
                for k, v in slot[field].items():
                    to[field][k] = to[field].get(k, 0.0) + v / n
    for slot in out.values():
        top = sorted(slot.pop("unscoped_ops").items(), key=lambda kv: -kv[1])[:5]
        slot["unscoped_top"] = [[name[:160], seconds] for name, seconds in top]
    return out


def read_file(path: str) -> dict:
    """The account of one ``.xplane.pb``, written beside it as
    ``<trace>.by_scope.json`` too. ``hlo_from``: ``capture`` where the file's
    own metadata plane held the programs' HLO, else ``hlo_scopes.json`` where
    the program left that file beside the capture, else None (every operation
    of a step program then reads ``unscoped``)."""
    from jax.profiler import ProfileData

    t0 = time.monotonic()
    spec = json.loads((BENCH / "trace_classes.json").read_text())
    dev, op_line, mod_line = (re.compile(spec[k]) for k in ("device_plane", "op_line", "module_line"))
    with open(path, "rb") as f:
        raw = f.read()
    hlo, hlo_from = hlo_scopes(raw), "capture"
    if not any(hlo.values()):
        side = Path(path).parent / "hlo_scopes.json"
        hlo, hlo_from = (json.loads(side.read_text()), side.name) if side.is_file() else ({}, None)
    vocab = load_vocabulary()
    per_device, busy = [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not dev.search(plane.name):
            continue
        lines: dict[str, list[Event]] = {"op": [], "mod": []}
        for line in plane.lines:
            key = "op" if op_line.search(line.name) else "mod" if mod_line.search(line.name) else None
            if key:
                lines[key] += [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in line.events]
        if lines["op"]:
            per_device.append(attribute(lines["op"], lines["mod"], hlo, vocab, spec["modules"]))
            busy.append(sum(slot["seconds"] for slot in per_device[-1].values()))
    out = {"file": path, "hlo_from": hlo_from, "hlo_modules": len(hlo),
           "busy_s": sum(busy) / max(len(busy), 1), "modules": merge(per_device)}
    out["reader_s"] = time.monotonic() - t0
    Path(re.sub(r"\.xplane\.pb$", "", path) + ".by_scope.json").write_text(json.dumps(out, indent=1))
    return out


@functools.lru_cache(maxsize=4)
def _read_in_child(path: str) -> dict:
    done = subprocess.run([sys.executable, str(Path(__file__).resolve()), path], capture_output=True, text=True,
                          timeout=240, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if done.returncode != 0:
        raise RuntimeError(f"bench/scope_times.py could not read {path}: {done.stderr[-2000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


def by_scope(trace: dict) -> dict | None:
    """``read_file`` of the trace the run reduced (its ``file``), once a run;
    None where there is no file or its HLO could not be had. A hand-built
    trace brings its own ``by_scope``."""
    got = trace.get("by_scope") or (_read_in_child(trace["file"]) if trace.get("file") else None)
    return got if got and got.get("hlo_from") else None


def class_share(sources: dict, cls: str) -> float | None:
    """100 x the class's self seconds in the two step programs over the
    trace's busy seconds; None where the class has no event there."""
    trace = sources.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    got = by_scope(trace)
    if got is None:
        return None
    slots = [got["modules"].get(key) or {} for key in STEP_MODULES]
    if not any(cls in (slot.get("class_s") or {}) for slot in slots):
        return None
    return 100.0 * sum((slot.get("class_s") or {}).get(cls, 0.0) for slot in slots) / trace["busy_s"]


if __name__ == "__main__":
    result = read_file(sys.argv[1])
    print(json.dumps(result))
