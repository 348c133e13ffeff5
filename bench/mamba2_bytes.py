"""Operations and bytes of a ``granitemoehybrid`` configuration's Mamba-2 scan
and of its mixed step, each a LOWER bound, and where the state-space
operations' device time is found in a trace: what the ``ssd_*`` readers and
``granite_mixed_step_mfu`` share. Sizes come from the configuration's file
alone, never from the program; what a step's rows were comes from the
program's own counters on the step's ``/debug/timeline`` record.

A ``granitemoehybrid`` configuration (``model_type`` in its file) has, by
``layer_types``, Mamba-2 layers (``mamba_n_heads`` Hh heads of ``mamba_d_head``
P with a float32 state of P x ``mamba_d_state`` N a head, one group, a
convolution of ``mamba_d_conv`` taps over Di + 2 N channels, Di = Hh P) and
attention layers, and in EVERY layer ``num_local_experts`` gated experts of
``intermediate_size`` (``num_experts_per_tok`` chosen) beside a shared gated MLP
of ``shared_intermediate_size``; a tied head over ``vocab_size``.

**The chunk kernel** (``ssd_chunk_kernel``), a visit of one packed block of Q
tokens (``deployment_sizes.ssd_block``) by one row, every head: the three block
products a head (2 P Q Q + 2 P N Q + 2 P Q N) and C B^T once (2 Q N Q; the
kernel computes it once a block of eight heads, counted once); its bytes: x in
and y out (Q x Di float32 each), B and C (Q x N). The states a row loads and
stores are left out (the record counts blocks, not rows). ``ssd_blocks`` of a
``mixed`` record is the visits over all Mamba layers.

**The one-token update**: a row's state read and written in every Mamba layer,
2 x Hh P N x 4 B a row a layer (``state_rows`` of a ``mixed`` record: its
one-token rows; of a ``decode`` record: its live rows summed over the steps
it ran). The program's update is the kernel ``ssm_state_update_kernel`` over
the rows that advance, one read and one write of each: this bound. (As two XLA
fusions over EVERY slot's state, read twice and written once, it reached 26%
of it: my chip run, PR 50.)

**A mixed step's matrix products** (``granite_mixed_step_mfu``), a packed token:
a Mamba layer's two projections 2 H (2 Di + 2 N + Hh) + 2 Di H, the attention
layer's four 2 H (2 Hq D + 2 Hkv D); every layer's chosen experts k x 3 x 2 H I
and shared MLP 3 x 2 H Is; the block products above by ``ssd_blocks``; attention
over the read keys 4 Hq D a (query, key) with every row counted as ONE query
over its context (``context_tokens``: a chunk's other queries are left out); the
head 2 H V for each of the step's rows (``batch``). The router, norms, the
convolution, the gate and the one-token update are elementwise or tiny and left
out. The packed tokens of a record are its ``attn_wide_tokens`` (the rows of
more than one token) and its ``state_rows`` (the one-token rows).

Every count leaves something out, which makes a share a lower bound: a reading
over 100% is a fault of this count, never of the program. Nothing where a
record lacks a counter (the parent commit).

The operations' device time is read off the trace file by ``mla_bytes.op_times``
(a child, ``hybrid_bytes.read_file``). An event of the operation line is the
whole HLO instruction, result shape first, and carries no ``named_scope``
(``hybrid_bytes``), so beside the kernel's own name the operations are known by
RESULTS only they have: the store and its entry (``f32[layers, slots, Hh, P, N]``,
``f32[slots, Hh, P, N]``), a row's x or y by head (``f32[slots, Hh, P]``); the
convolution's tails and windows (``f32[.., slots, K - 1 | K, Di + 2 N]``) and the
packed tokens' channels (``[tokens, Di + 2 N]``); the kernel's transposed
operands (``f32[Di, padded tokens]``). Never a loop or a tuple.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
CHUNK_KERNEL = "ssd_chunk_kernel"
UPDATE_KERNEL = "ssm_state_update"
NOT_A_LOOP = r"^(?!%?(?:while|cond|conditional|call|tuple|get-tuple-element)[\w.\-]* = )"


def is_granite(config: dict) -> bool:
    return config.get("model_type") == "granitemoehybrid"


def sizes(config: dict) -> dict:
    dep = config["deployment_sizes"]
    H, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    kinds = list(config["layer_types"])
    Hh, P, N = int(config["mamba_n_heads"]), int(config["mamba_d_head"]), int(config["mamba_d_state"])
    return {"H": H, "Hq": heads, "Hkv": int(config["num_key_value_heads"]), "D": int(config.get("head_dim") or H // heads),
            "V": int(config["vocab_size"]), "I": int(config["intermediate_size"]),
            "Is": int(config["shared_intermediate_size"]), "E": int(config["num_local_experts"]),
            "k": int(config["num_experts_per_tok"]), "Hh": Hh, "P": P, "N": N, "Di": Hh * P,
            "K": int(config["mamba_d_conv"]), "mamba": kinds.count("mamba"), "attention": kinds.count("attention"),
            "L": len(kinds), "Q": int(dep["ssd_block"]), "slots": int(dep["max_slots"]),
            "tokens": int(dep["mixed_step_tokens"]), "s": DTYPE_BYTES[dep.get("state_dtype", "float32")]}


def state_bytes_per_row_layer(config: dict) -> int:
    """A slot's matrix state in ONE Mamba layer: 4,194,304 B at the published sizes."""
    s = sizes(config)
    return s["Hh"] * s["P"] * s["N"] * s["s"]


def block_flops(config: dict) -> int:
    """The matrix products of ONE visit of a packed block, every head."""
    s = sizes(config)
    Q, P, N = s["Q"], s["P"], s["N"]
    return s["Hh"] * (2 * P * Q * Q + 2 * P * N * Q + 2 * P * Q * N) + 2 * Q * N * Q


def block_bytes(config: dict) -> int:
    """What ONE visit moves: x in, y out, B and C, float32."""
    s = sizes(config)
    return (2 * s["Q"] * s["Di"] + 2 * s["Q"] * s["N"]) * 4


def chunk_work(config: dict, records) -> tuple[float, float] | None:
    """(operations, bytes) of the chunk kernel over ``records``; None where a
    ``mixed`` record lacks ``ssd_blocks`` or none walked a block."""
    mixed = [t for t in records or [] if t.get("kind") == "mixed"]
    if not mixed or any(t.get("ssd_blocks") is None for t in mixed):
        return None
    blocks = sum(t["ssd_blocks"] for t in mixed)
    return (blocks * block_flops(config), blocks * block_bytes(config)) if blocks else None


def update_bytes(config: dict, records) -> float | None:
    """The bytes the one-token update must move over ``records`` (``mixed`` and
    ``decode``); None where one lacks ``state_rows`` or no row was served."""
    steps = [t for t in records or [] if t.get("kind") in ("mixed", "decode")]
    if not steps or any(t.get("state_rows") is None for t in steps):
        return None
    rows = sum(t["state_rows"] for t in steps)
    return rows * sizes(config)["mamba"] * 2 * state_bytes_per_row_layer(config) if rows else None


def token_flops(config: dict) -> int:
    """The weight products of ONE packed token through every layer."""
    s = sizes(config)
    mixer = 2 * s["H"] * (2 * s["Di"] + 2 * s["N"] + s["Hh"]) + 2 * s["Di"] * s["H"]
    attention = 2 * s["H"] * (2 * s["Hq"] * s["D"] + 2 * s["Hkv"] * s["D"])
    ffn = s["k"] * 3 * 2 * s["H"] * s["I"] + 3 * 2 * s["H"] * s["Is"]
    return s["mamba"] * mixer + s["attention"] * attention + s["L"] * ffn


def mixed_step_flops(config: dict, record: dict) -> float | None:
    """The lower bound for the step a ``mixed`` record describes; None where
    the record lacks a counter."""
    need = ("ssd_blocks", "state_rows", "attn_wide_tokens", "context_tokens", "batch")
    if any(record.get(k) is None for k in need):
        return None
    s = sizes(config)
    tokens = record["attn_wide_tokens"] + record["state_rows"]
    return (tokens * token_flops(config) + record["ssd_blocks"] * block_flops(config)
            + s["attention"] * 4 * s["Hq"] * s["D"] * record["context_tokens"] + record["batch"] * 2 * s["H"] * s["V"])


def _shape(*dims) -> str:
    return ",".join(str(d) for d in dims)


def update_pattern(config: dict) -> str:
    """The one-token update: its kernel by name (``ssm_state_update_*``), or a
    result only it has: the store, a layer's entry of it, or a row's x / y by
    head. The chunk kernel returns the store too and is named apart."""
    s = sizes(config)
    S, Hh, P, N = s["slots"], s["Hh"], s["P"], s["N"]
    shapes = [_shape(s["mamba"], S, Hh, P, N), _shape(S, Hh, P, N), _shape(S, Hh, P)]
    return (NOT_A_LOOP + rf"(?:%?{UPDATE_KERNEL}\w*\b|%?(?!{CHUNK_KERNEL})[\w.\-]+ = f32\[(?:" + "|".join(shapes)
            + r")\])")


def conv_pattern(config: dict) -> str:
    """A result only the convolution has: the tails' store, its entry, a window,
    or the packed tokens' Di + 2 N channels."""
    s = sizes(config)
    C, S, K = s["Di"] + 2 * s["N"], s["slots"], s["K"]
    shapes = [_shape(s["mamba"], S, K - 1, C), _shape(S, K - 1, C), _shape(S, K, C),
              rf"(?:1,)?(?:{s['tokens']}|{S}),{C}"]
    return NOT_A_LOOP + r"%?[\w.\-]+ = (?:f32|bf16)\[(?:" + "|".join(shapes) + r")\]"


def scan_pattern(config: dict) -> str:
    """Every state-space operation: the chunk kernel by name, its transposed
    operands, the one-token update, the convolution."""
    s = sizes(config)
    padded = -(-s["tokens"] // s["Q"]) * s["Q"]
    laid = rf"%?[\w.\-]+ = f32\[(?:{s['Di']},{padded}|{padded},{s['Di']}|{padded},{s['Hh']},{s['P']})\]"
    strip = lambda p: p[len(NOT_A_LOOP):]
    return (NOT_A_LOOP + rf"(?:%?{CHUNK_KERNEL}\b|{laid}|{strip(update_pattern(config))}|{strip(conv_pattern(config))})")
