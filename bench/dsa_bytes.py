"""What the readers of a learned sparse attention share: its sizes from the
configuration's file alone (never from the program), a cached token's bytes,
and where its operations are found in a trace.

A configuration with an indexer (``sa_config`` in its file: ``topk``,
``indexer_num_heads`` Hi, ``indexer_head_dim`` Di) holds, beside a token's keys
and values (2 x kv heads x head size x the cache type a layer), one indexer key
(Di x the cache type a layer).

The program adds no kernel of its own for it (``/debug/status`` ``dsa`` says how
selection and read are done), so there is no kernel to take a roofline share
of. Its operations' device time is read off the trace file by a child (``python
bench/dsa_bytes.py <file.xplane.pb> <pattern>``, ``JAX_PLATFORMS=cpu``), as
``hybrid_bytes.py`` finds the state's: an event of the operation line is the
whole HLO instruction with the shapes of its result and operands, and the
program's ``jax.named_scope`` paths (``dsa.index``, ``dsa.select``,
``dsa.attend``) are not in the profile (my chip runs, PR 32 and PR 36), so the
operations are known by the shapes only they have, from the file's sizes and
the step programs' two tile sizes (``dsa_pattern``): the indexer's stacked
weights and its projections of a step's tokens; the indexer's keys by page
(``bf16[pages, Di, page size]``: the pool as an operand or as the result of
the key's write, a row's gathered pages, a block's); a one-query row's scores
over its whole page table, the chosen positions, the gathered keys and values
and the attention over them; a prompt chunk's tiles (the [TILE, S'] score
buffer, keys and mask, S' the row rounded up to whole blocks; a block's keys
and values; the running softmax's [kv heads, group, TILE, ...] state); or a
kernel named ``dsa_*``. Loops and conditionals are passed over (the layer
loop's carry holds every pool; a body's operations are events of their own),
and the union of the matching intervals is taken. Held against the scopes of
the step programs compiled for a described v5e (sandbox, PR 36: every
operation under a ``dsa.*`` scope longer than an index computation is found,
and nothing outside the sparse branch but the kernels' own padded query).
``TILE`` and ``BLOCK`` repeat ``ops/sparse_attention.py``'s: where the program
changes them the tile patterns find nothing, and the reader then says nothing
rather than a share that has fallen to the one-query rows' part
(``dsa_times``: every part of ``dsa_shapes`` has to find an event); where the
program has no such operation at all (the parent commit) it says nothing
either. What was counted is left beside the trace file
(``<file>.dsa_ops.json``: events in all and by part, the five longest).
"""

from __future__ import annotations

import functools
import json
import os
import re
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
TILE, BLOCK = 128, 1024   # queries a tile, keys a block: ops/sparse_attention.TILE, BLOCK_TOKENS


def is_dsa(config: dict) -> bool:
    return "sa_config" in config


def sizes(config: dict) -> dict:
    sa, dep = config["sa_config"], config["deployment_sizes"]
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    page = int(dep["page_size"])
    pages = -(-int(dep["max_seq_len"]) // page)
    block = min(max(BLOCK // page, 1), pages) * page
    return {"H": int(config["hidden_size"]), "L": int(config["num_hidden_layers"]), "Hq": heads, "Hkv": kv_heads,
            "D": int(config.get("head_dim") or int(config["hidden_size"]) // heads),
            "Hi": int(sa["indexer_num_heads"]), "Di": int(sa["indexer_head_dim"]), "topk": int(sa["topk"]),
            "slots": int(dep["max_slots"]), "page": page, "S": pages * page, "block": block,
            "S_tiles": -(-pages * page // block) * block,
            "tokens": int(dep.get("mixed_step_tokens", 512 + int(dep["max_slots"]))),
            "c": DTYPE_BYTES[dep["kv_dtype"]]}


def cache_token_bytes(config: dict) -> dict:
    """One cached token of ONE layer: keys and values, and the indexer's key."""
    s = sizes(config)
    return {"kv": 2 * s["Hkv"] * s["D"] * s["c"], "index": s["Di"] * s["c"]}


def dsa_shapes(config: dict) -> dict[str, list[str]]:
    """The shapes only the stage has, as patterns, by the part of it that
    holds them: ``indexer`` (both step programs), ``row`` (a row of one
    query), ``tile`` (a tile of a prompt chunk: these follow ``TILE`` and
    ``BLOCK``)."""
    s = sizes(config)
    G, page = s["Hq"] // s["Hkv"], s["page"]
    B, T, topk, Hi, Di, Hkv, D = s["slots"], s["tokens"], s["topk"], s["Hi"], s["Di"], s["Hkv"], s["D"]
    ppb = s["block"] // page
    alt = lambda *values: "(?:" + "|".join(str(v) for v in dict.fromkeys(values)) + ")"
    dims = lambda *shape: r"\[" + ",".join(map(str, shape)) + r"\]"
    pages = alt(s["S"] // page, s["S_tiles"] // page)      # a row of the page table, and rounded up to whole blocks
    row = alt(s["S"], s["S_tiles"])
    n = alt(B, T)                                          # the indexer's side of a step's tokens: decode rows, packed tokens
    return {
        # the indexer's stacked weights and its projections of a step's tokens (not [n, Di]: the rotary's
        # table is as wide where Di is half a head), its keys by page (pool, gathered pages, a block)
        "indexer": [dims(s["L"], s["H"], alt(Hi * Di, Di, Hi)), dims(n, Hi * Di), dims(n, Hi, Di), dims(n, Hi, Di // 2),
                    dims(n, Hi), r"bf16" + dims(r"\d+", Di, page), dims(B, pages, Di, page)],
        # a row of one query: scores over its whole page table, the chosen positions, their keys and values
        "row": [dims(B, row), dims(B, pages, page), dims(B, 1, row), r"(?:s32|pred)" + dims(B, topk), dims(B * topk),
                dims(B * topk, Hkv * D), dims(B, topk, Hkv * D), dims(B, topk, Hkv, D), dims(B, Hkv, G, topk)],
        # a tile of a prompt chunk: its queries, its score buffer and mask, a block of keys, the running softmax
        "tile": [dims(TILE, row), dims(TILE, Hi, Di), dims(TILE, Hi), dims(TILE, ppb, page), dims(TILE, s["block"]),
                 r"u32" + dims(TILE), dims(ppb, page, Hkv * D), dims(s["block"], Hkv, D), dims(Hkv, G, TILE, s["block"]),
                 dims(Hkv, G, TILE, D), r"f32" + dims(Hkv, G, TILE), dims(TILE, Hkv, G, D), r"s32" + dims(T + TILE)],
    }


NOT_A_BODY = r"^(?!.* (?:while|conditional|call)\()"   # loops and conditionals: their bodies' operations are events of their own


def dsa_pattern(config: dict) -> str:
    """What an operation of the scoring, the selection or the sparse read holds
    in its HLO text, result or operand (a trace's event carries both with their
    shapes). Loops and conditionals are left out: their bodies' operations are
    events of their own, and the layer loop's carry holds every pool."""
    shapes = [p for part in dsa_shapes(config).values() for p in part]
    return NOT_A_BODY + r"(?:%?dsa_\w+|.*(?:" + "|".join(shapes) + "))"


def _union_s(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def read_file(path: str, pattern: str, parts: dict[str, str] | None = None) -> dict:
    """Seconds the operations whose HLO text ``pattern`` finds ran (union of
    their intervals on a device's operation line), averaged over the devices,
    how many events, how many of them each of ``parts`` {name: pattern} finds,
    and the five that took longest (for a reader's eye)."""
    from jax.profiler import ProfileData

    dsa_op = re.compile(pattern)
    by_part = {name: re.compile(p) for name, p in (parts or {}).items()}
    spec = json.loads((BENCH / "trace_classes.json").read_text())
    dev, op_line = re.compile(spec["device_plane"]), re.compile(spec["op_line"])
    per_device, events, longest, found = [], 0, {}, dict.fromkeys(by_part, 0)
    for plane in ProfileData.from_file(path).planes:
        if not dev.search(plane.name):
            continue
        hits, seen = [], False
        for line in plane.lines:
            if not op_line.search(line.name):
                continue
            for ev in line.events:
                seen = True
                if dsa_op.search(ev.name):
                    hits.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
                    longest[ev.name[:160]] = longest.get(ev.name[:160], 0.0) + ev.duration_ns * 1e-9
                    for name, part in by_part.items():
                        found[name] += bool(part.search(ev.name))
        if seen:
            per_device.append(_union_s(hits))
            events += len(hits)
    n = max(len(per_device), 1)
    return {"dsa_s": sum(per_device) / n, "dsa_events": events, "events_by_part": found,
            "longest": sorted(longest.items(), key=lambda kv: -kv[1])[:5]}


@functools.lru_cache(maxsize=4)
def _read_in_child(path: str, pattern: str, parts: str) -> dict:
    done = subprocess.run([sys.executable, str(Path(__file__).resolve()), path, pattern, parts], capture_output=True,
                          text=True, timeout=240, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if done.returncode != 0:
        raise RuntimeError(f"bench/dsa_bytes.py could not read {path}: {done.stderr[-2000:]}")
    found = json.loads(done.stdout.strip().splitlines()[-1])
    try:   # the run's record of what was counted: events by part, the five longest
        Path(path + ".dsa_ops.json").write_text(json.dumps(found, indent=1))
    except OSError:
        pass
    return found


def dsa_times(trace: dict, config: dict) -> dict | None:
    """``read_file`` of the trace the run reduced (its ``file``), once a run,
    left beside that file as ``<file>.dsa_ops.json`` (events by part, the five
    longest operations); None for a configuration without an indexer, or
    where the trace holds no such operation: a program without the layer.
    None too where a part that was counted finds nothing (a program whose
    ``TILE`` or ``BLOCK`` are no longer this file's finds no tile operation):
    the share would then be less than the stage's and not say so. A
    hand-built trace brings its own ``dsa_ops``."""
    if not is_dsa(config):
        return None
    times = trace.get("dsa_ops")
    if times is None and trace.get("file"):
        parts = {name: NOT_A_BODY + ".*(?:" + "|".join(shapes) + ")" for name, shapes in dsa_shapes(config).items()}
        times = _read_in_child(trace["file"], dsa_pattern(config), json.dumps(parts, sort_keys=True))
    if not times or times["dsa_events"] <= 0 or not all((times.get("events_by_part") or {"": 1}).values()):
        return None
    return times


def read_share(records) -> float | None:
    """100 x tokens whose keys and values the attention read / tokens the
    queries could see, over the ``decode`` and ``mixed`` records that carry the
    program's two counts; None while none does."""
    recs = [t for t in records or [] if t.get("kind") in ("decode", "mixed") and t.get("dsa_context_tokens")]
    seen = sum(t["dsa_context_tokens"] for t in recs)
    return 100.0 * sum(t["dsa_kv_tokens_read"] for t in recs) / seen if seen else None


if __name__ == "__main__":
    print(json.dumps(read_file(sys.argv[1], sys.argv[2], json.loads(sys.argv[3]) if len(sys.argv) > 3 else None)))
