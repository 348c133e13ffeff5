"""Bytes and operations a step of an ``evabyte`` configuration must move and make,
LOWER bounds, and what its rows read of their contexts: what the ``eva_*`` and
``evabyte_*`` readers share. Sizes come from the configuration's file alone, never
from the program; what a step's rows held and read comes from the program's own
counters on the ``decode`` and ``mixed`` records of ``/debug/timeline``:

- ``eva_rows``: the rows of the page pool the step's rows read, summaries and exact
  rows alike, summed over the rows (and over a decode record's ``steps``);
- ``eva_context``: the same rows' contexts in tokens, what full attention would read;
- ``eva_rolls``: the windows folded into a page of summaries;
- ``eva_pairs``: the (query, cached row) pairs the attention scored: a decode row's
  one query sees its rows, a chunk's queries one row more each;
- ``eva_queries``: the step's queries, its packed tokens.

An ``evabyte`` configuration (``model_type`` in its file) is a dense decoder with NO
grouped heads: a layer's q, k, v, o (4 x hidden x hidden) and gate, up, down (3 x
hidden x intermediate), a head of ``vocab_size`` x ``num_pred_heads`` columns. A cached
row is a key and a value of ``hidden_size`` values a layer.

A decode step reads once: every matrix at the served weight type and the head; the
``eva_rows`` of its rows in every layer at the cache type. Scales, norms, the two EVA
vectors, activations, the embedding's rows and the rows a step writes are left out: a
share over 100% is a fault of this count, never of the program. A mixed step's
operations (2 a multiply-add): every packed token through every matrix, the head a
row, and a (query, row, head) pair's score and weighted value, 4 x head_dim each.
The fold's own reads and sums (once per 2,048 tokens a row) are left out of both.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
COUNTERS = ("eva_rows", "eva_context", "eva_rolls", "eva_pairs", "eva_queries")


def is_eva(config: dict) -> bool:
    return config.get("model_type") == "evabyte"


def sizes(config: dict) -> dict:
    dep = config["deployment_sizes"]
    return {"H": int(config["hidden_size"]), "L": int(config["num_hidden_layers"]), "I": int(config["intermediate_size"]),
            "heads": int(config["num_attention_heads"]), "V": int(config["vocab_size"]) * int(config["num_pred_heads"]),
            "W": int(config["window_size"]), "C": int(config["chunk_size"]), "steps": int(dep["decode_chunk"]),
            "w": DTYPE_BYTES[dep["weight_dtype"]], "kv": DTYPE_BYTES[dep["kv_dtype"]]}


def matrix_params(config: dict) -> int:
    """Every layer's seven matrices."""
    s = sizes(config)
    return s["L"] * (4 * s["H"] * s["H"] + 3 * s["H"] * s["I"])


def read_share(records) -> float | None:
    """100 x rows of the pool read / tokens of context, over the ``decode`` and ``mixed``
    records that carry the program's two counts; None while none does."""
    recs = [t for t in records or [] if t.get("kind") in ("decode", "mixed") and t.get("eva_context")]
    seen = sum(t["eva_context"] for t in recs)
    return 100.0 * sum(t["eva_rows"] for t in recs) / seen if seen else None


def decode_step_bytes(config: dict, record: dict) -> float | None:
    """Bytes ONE step of a decode chunk must read, from a ``decode`` record; None where
    it lacks a counter or ran no step."""
    if any(record.get(k) is None for k in COUNTERS) or not record.get("steps"):
        return None
    s = sizes(config)
    weights = (matrix_params(config) + s["H"] * s["V"]) * s["w"]
    rows = record["eva_rows"] / record["steps"]           # the step's rows' cached rows, a key and a value a layer
    return weights + rows * 2 * s["H"] * s["kv"] * s["L"]


def mixed_step_flops(config: dict, record: dict) -> float | None:
    """Operations of a mixed step's matrix products, from a ``mixed`` record; None where
    it lacks a counter."""
    if any(record.get(k) is None for k in COUNTERS) or record.get("batch") is None:
        return None
    s = sizes(config)
    head = record["batch"] * s["H"] * s["V"]
    attention = 2 * s["H"] * record["eva_pairs"] * s["L"]   # q.k and p.v: 2 x heads x head_dim a pair a layer
    return 2.0 * (record["eva_queries"] * matrix_params(config) + head + attention)


def step_share(sources: dict, kind: str, count, seconds: float | None, peak: str) -> float | None:
    """``count(config, record)`` over the chip's ``peak`` a second, as a share (%) of
    ``seconds`` (a step program's median device time): the median over the ``kind``
    records dispatched inside the capture, as the time is a median. None for another
    configuration, without a trace, or where a record lacks a counter."""
    from stats import dispatched_in_capture, median

    if not seconds or not is_eva(sources["config"]):
        return None
    records = dispatched_in_capture(sources)
    if records is None:
        return None
    counts = [count(sources["config"], t) for t in records if t.get("kind") == kind]
    if not counts or any(c is None for c in counts):
        return None
    return 100.0 * (median(counts) / sources["chip"][peak]) / seconds


def fold_pattern(config: dict) -> str:
    """What an operation of the fold holds in its HLO text, result or operand: the shapes
    only the fold has. A window's rows gathered ([window, hidden] and by head), a window
    by chunks ([window / chunk, chunk, heads, ...]: the pooling's logits, weights and
    products) and the summaries by head ([window / chunk, heads, head_dim]). Loops are
    left out: their bodies' operations are events of their own."""
    from dsa_bytes import NOT_A_BODY

    s = sizes(config)
    W, C, heads, D = s["W"], s["C"], s["heads"], s["H"] // s["heads"]
    dims = lambda *d: r"\[" + ",".join(str(x) for x in d) + r"[\],]"
    shapes = [dims(W, s["H"]), dims(W, heads, D), dims(W // C, C, heads), dims(W // C, heads, D)]
    return NOT_A_BODY + ".*(?:" + "|".join(shapes) + ")"


def fold_times(trace: dict, config: dict) -> dict | None:
    """Seconds the fold's operations ran in the trace the run reduced (``dsa_bytes``'s
    reader of a capture by pattern: the union of their intervals on a device's operation
    line), with their number: 0 where no row started a window inside the capture. None
    for another configuration or without a trace's file. A hand-built trace brings its
    own ``eva_fold_ops``."""
    if not is_eva(config):
        return None
    found = trace.get("eva_fold_ops")
    if found is None and trace.get("file"):
        from dsa_bytes import _read_in_child

        found = _read_in_child(trace["file"], fold_pattern(config), "{}")
    if not found:
        return None
    return {"fold_s": found["dsa_s"], "fold_events": found["dsa_events"], "longest": found.get("longest")}
