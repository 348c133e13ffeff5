"""Bytes ONE step of a ``xing4_0`` configuration must read, a LOWER bound, the
operations of its absorbed latent reads, and where its two new mechanisms'
device time is found in a trace: what the ``mla_*`` and ``mhc_busy_share``
readers share. Sizes come from the configuration's file alone, never from the
program; what a step's rows chose and walked comes from the program's own
counters on the step's ``/debug/timeline`` record.

A ``xing4_0`` configuration (``model_type`` in its file) has
``first_k_dense_replace`` leading layers with a dense SwiGLU of
``intermediate_size`` and then expert layers with ``n_routed_experts`` SwiGLU
experts of ``moe_intermediate_size`` and ``n_shared_experts`` always-on ones;
every layer a latent attention of five matrices (W_DQ hidden x q_lora_rank,
W_UQ q_lora_rank x heads x (nope + rope), W_DKV hidden x (kv_lora_rank + rope),
W_UKV kv_lora_rank x heads x (nope + v), W_O heads x v x hidden) and two blocks'
hyper-connection projections (hc_mult x hidden x (2 hc_mult + hc_mult^2),
float32). A step reads once, at the served weight type:

- every layer's attention matrices and both blocks' hyper-connection projections;
- the dense layers' three MLP matrices;
- in every expert layer the three matrices of each DISTINCT expert its rows
  chose (the record's ``experts_read``: a mean over the expert layers) and of
  the shared expert, and the router at the activation type;
- the head (hidden x vocabulary); the embedding's rows are left out;
- latent pages: ``latent_pages_read`` (the record's count over all layers of the
  pages its rows' tiles walked) x page size x (kv_lora_rank + rope) values at the
  cache type: 1,152 B a token at the published sizes. The pool stores and moves
  a row in 640 lanes (1,280 B); the 64 pad lanes are left out of the bound.

Scales, norms, biases, activations, the streams' own traffic and the rows a step
writes are left out, which makes a share a lower bound: a reading over 100% is a
fault of this count, never of the program. Nothing where a record lacks a
counter: no number of experts is ever assumed.

The absorbed read's operations, a (query, cached token, head): 2 x (kv_lora_rank
+ rope) for the score and 2 x kv_lora_rank for the weighted latent; a page
walked by a tile of W queries costs page size x W x heads of them. One-query
rows' pages are the record's ``latent_row_pages_read`` (W = 1); the rest of
``latent_pages_read`` is the chunks' tiles' (W = the plan's ``wide_tile``,
``status.ragged_attention``; a tile's dead tail is counted as the kernel
computes it).
"""

from __future__ import annotations

import json
from pathlib import Path

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
COUNTERS = ("experts_read", "latent_pages_read", "latent_row_pages_read")
DECODE_KERNEL = "mla_decode_attn_kernel"


def is_xing(config: dict) -> bool:
    return config.get("model_type") == "xing4_0"


def sizes(config: dict) -> dict:
    dep = config["deployment_sizes"]
    return {"H": int(config["hidden_size"]), "L": int(config["num_hidden_layers"]),
            "Hq": int(config["num_attention_heads"]), "V": int(config["vocab_size"]),
            "q_rank": int(config["q_lora_rank"]), "rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]), "rope": int(config["qk_rope_head_dim"]),
            "v": int(config["v_head_dim"]), "n": int(config["hc_mult"]),
            "I_dense": int(config["intermediate_size"]), "I": int(config["moe_intermediate_size"]),
            "E": int(config["n_routed_experts"]), "shared": int(config["n_shared_experts"]),
            "dense_layers": int(config["first_k_dense_replace"]), "page": int(dep["page_size"]),
            "slots": int(dep["max_slots"]), "tokens": int(dep["mixed_step_tokens"]),
            "w": DTYPE_BYTES[dep["weight_dtype"]], "a": DTYPE_BYTES[dep["activation_dtype"]],
            "c": DTYPE_BYTES[dep["kv_dtype"]]}


def expert_bytes(config: dict) -> int:
    """One expert's three matrices at the served weight type."""
    s = sizes(config)
    return 3 * s["H"] * s["I"] * s["w"]


def attention_bytes(config: dict) -> int:
    """One layer's five attention matrices."""
    s = sizes(config)
    return (s["H"] * s["q_rank"] + s["q_rank"] * s["Hq"] * (s["nope"] + s["rope"]) + s["H"] * (s["rank"] + s["rope"])
            + s["rank"] * s["Hq"] * (s["nope"] + s["v"]) + s["Hq"] * s["v"] * s["H"]) * s["w"]


def hyper_bytes(config: dict) -> int:
    """One layer's two float32 hyper-connection projections."""
    s = sizes(config)
    return 2 * s["n"] * s["H"] * (2 * s["n"] + s["n"] ** 2) * 4


def latent_token_bytes(config: dict) -> int:
    """What a cached token's row holds, ONE layer: the latent and the rotary key."""
    s = sizes(config)
    return (s["rank"] + s["rope"]) * s["c"]


def fixed_weight_bytes(config: dict) -> int:
    """What every step reads whatever its rows chose."""
    s = sizes(config)
    expert_layers = s["L"] - s["dense_layers"]
    return (s["L"] * (attention_bytes(config) + hyper_bytes(config))
            + s["dense_layers"] * 3 * s["H"] * s["I_dense"] * s["w"]
            + expert_layers * (s["shared"] * expert_bytes(config) + s["H"] * s["E"] * s["a"])
            + s["H"] * s["V"] * s["w"])


def counted(record: dict) -> bool:
    return all(record.get(k) is not None for k in COUNTERS)


def step_bytes(config: dict, record: dict) -> float | None:
    """The lower bound for the step a ``mixed`` record describes; None where the
    record lacks a counter."""
    if not counted(record):
        return None
    s = sizes(config)
    return (fixed_weight_bytes(config) + record["experts_read"] * (s["L"] - s["dense_layers"]) * expert_bytes(config)
            + record["latent_pages_read"] * s["page"] * latent_token_bytes(config))


def row_read(config: dict, records) -> tuple[float, float] | None:
    """(bytes, operations) of the one-query rows' absorbed reads over
    ``records``; None where one lacks the counter or none walked a page."""
    if not records or any(t.get("latent_row_pages_read") is None for t in records):
        return None
    s = sizes(config)
    tokens = sum(t["latent_row_pages_read"] for t in records) * s["page"]
    flops = tokens * s["Hq"] * 2 * (2 * s["rank"] + s["rope"])
    return (tokens * latent_token_bytes(config), flops) if tokens else None


def kernel_pattern(name: str) -> str:
    """A kernel's event on the operation line: its own name first."""
    return rf"^%?{name}\b"


def attention_pattern(config: dict) -> str:
    """What an operation of the latent attention holds in its HLO text: a kernel
    of its name, or an array a head wide by the latent's row (the absorbed
    queries, the weighted latents: [.., heads, rank + rope | lanes | rank]) or
    a token's pool row ([tokens, lanes]). Never a loop or a branch, whose event
    spans everything inside it. The five matrices' products have the shapes of
    any projection and are NOT counted: a lower bound."""
    s = sizes(config)
    width = s["rank"] + s["rope"]
    lanes = -(-width // 128) * 128
    heads = rf"\[(?:\d+,)*{s['Hq']},(?:{width}|{lanes}|{s['rank']})\]"
    return (r"^(?!%?(?:while|cond|conditional|call)[\w.\-]* = )(?:%?mla_\w+_attn_kernel\b|.*(?:" + heads
            + rf"|bf16\[\d+,{lanes}\]))")


def hyper_pattern(config: dict) -> str:
    """What an operation of the hyper-connections holds: its Sinkhorn kernel by
    name (``mhc_sinkhorn_kernel``), the streams stream-major ([n, (1,) tokens,
    hidden] at the step's packed width or its slots), a block's projection ([n x
    hidden | n, hidden, 2n + n^2]), or the maps with the tokens in the lanes
    (float32 [2n + n^2 | n^2 | n | n,n , tokens]). One stream alone has any
    activation's shape and is NOT counted: a lower bound. Never a loop or a
    branch."""
    s = sizes(config)
    n, H, maps = s["n"], s["H"], 2 * s["n"] + s["n"] ** 2
    widths = "|".join(str(t) for t in sorted({s["tokens"], s["slots"]}))
    lead = "|".join([str(maps), str(n * n), f"{n},{n}", str(n)])
    return (r"^(?!%?(?:while|cond|conditional|call)[\w.\-]* = )(?:%?mhc_\w+|.*(?:"
            + rf"\[{n},(?:1,)?(?:{widths}),{H}\]|\[(?:{n * H}|{n},{H}),{maps}\]|f32\[(?:{lead}),(?:{widths})\]))")


def op_times(trace: dict, key: str, pattern: str) -> dict | None:
    """Seconds the operations ``pattern`` finds ran (union of their intervals on
    a device's operation line) and how many events, read from the trace the run
    reduced (its ``file``) in a child (``hybrid_bytes.read_file``); left beside
    the trace as ``<file>.<key>.json``. A hand-built trace brings its own
    ``trace[key]``. None where the trace holds no such operation: a program
    without the mechanism (the parent commit)."""
    times = trace.get(key)
    if times is None and trace.get("file"):
        from hybrid_bytes import _read_in_child

        found = _read_in_child(trace["file"], pattern)
        times = {"seconds": found["ssm_s"], "events": found["ssm_events"], "pattern": pattern}
        Path(trace["file"] + f".{key}.json").write_text(json.dumps(times))
    return times if times and times["events"] > 0 else None
