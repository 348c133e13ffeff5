"""Operations and bytes of a ``kimi_linear`` configuration's Kimi Delta
Attention (KDA) and of its mixed step, each a LOWER bound, and where the
state operations' device time is found in a trace: what the ``kda_*`` readers
and ``kimi_mixed_step_mfu`` share. Sizes come from the configuration's file
alone, never from the program; what a step's rows were comes from the
program's own counters on the step's ``/debug/timeline`` record.

A ``kimi_linear`` configuration (``model_type`` in its file) has, by
``linear_attn_config``, KDA layers (``num_heads`` Hh heads of ``head_dim`` D
with a float32 state of D x D a head, a convolution of
``short_conv_kernel_size`` taps over 3 P channels, P = Hh D) and latent
attention layers (``num_attention_heads`` Hq heads of ``qk_nope_head_dim`` +
``qk_rope_head_dim`` over a latent of ``kv_lora_rank``, values of
``v_head_dim``); ``first_k_dense_replace`` leading layers with a dense MLP of
``intermediate_size``, every other with ``routed_experts`` routed experts of
``moe_intermediate_size`` (``num_experts_per_token`` chosen) of which this chip
HOLDS ``num_experts``, beside ``num_shared_experts`` shared; an untied head
over ``vocab_size``.

**The chunk kernel** (``kda_chunk_kernel``), a visit of one packed block of Q
tokens (``deployment_sizes.kda_block``) by one row, a head: the three products
with the state (the correction's and the output's reads of S_in, the state's
growth: 3 x 2 Q D D) and the pairs below the diagonal counted ONCE each for L,
for A, for the solve and for A U (4 x Q Q D; the kernel's products cover the
whole Q x Q square and its solve is log2 Q products of it: more work, not
counted). Its bytes: q, k, v and g in and o out (Q x P float32 each). The
states a row loads and stores are left out (the record counts blocks, not
rows). ``kda_blocks`` of a ``mixed`` record is the visits over all KDA layers.

**The one-token update**: a row's state read and written in every KDA layer,
2 x Hh D D x 4 B a row a layer (``state_rows`` of a ``mixed`` record: its
one-token rows; of a ``decode`` record: its live rows summed over the steps it
ran): the kernel ``kda_state_update_kernel`` over the rows that advance, one
read and one write of each.

**A mixed step's matrix products** (``kimi_mixed_step_mfu``), a packed token:
a KDA layer's projections 2 H 3 P + 2 P H + 2 x 2 (H r + r P) + 2 H Hh (r =
D), a latent layer's 2 H Hq (Dn + Dr) + 2 H (rank + Dr) + the absorbed queries
and outputs 2 Hq rank (Dn + Dv) + 2 Hq Dv H; a dense layer's MLP 3 x 2 H Id,
an expert layer's shared expert 3 x 2 H I; the HELD experts' rows, 3 x 2 H I
each, by the record's own count (``expert_tokens_mean`` is the mean over ALL
routed experts of the rows an expert multiplied, the experts this chip does
not hold at 0: times ``routed_experts`` it is the (token, held expert) pairs a
layer); the chunk form's products above by ``kda_blocks``; the latent read 2
Hq (2 rank + Dr) a (query, key) with every row counted as ONE query over its
context (``context_tokens``); the head 2 H V for each of the step's rows
(``batch``). The router, norms, the convolution, the gates and the one-token
update are elementwise or tiny and left out. The packed tokens of a record are
its ``attn_wide_tokens`` (the rows of more than one token) and its
``state_rows`` (the one-token rows).

Every count leaves something out, which makes a share a lower bound: a reading
over 100% is a fault of this count, never of the program. Nothing where a
record lacks a counter (the parent commit).

The operations' device time is read off the trace file by
``mla_bytes.op_times``: the two kernels by name; the convolution by RESULTS
only it has (the tails' store and its entry, a window, the packed tokens' 3 P
channels); the chunk kernel's padded operands ([padded tokens, P]).
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
CHUNK_KERNEL = "kda_chunk_kernel"
UPDATE_KERNEL = "kda_state_update"
NOT_A_LOOP = r"^(?!%?(?:while|cond|conditional|call|tuple|get-tuple-element)[\w.\-]* = )"


def is_kimi(config: dict) -> bool:
    return config.get("model_type") == "kimi_linear"


def sizes(config: dict) -> dict:
    dep, lin = config["deployment_sizes"], config["linear_attn_config"]
    Hh, D = int(lin["num_heads"]), int(lin["head_dim"])
    L, lead = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    return {"H": int(config["hidden_size"]), "V": int(config["vocab_size"]), "Hh": Hh, "D": D, "P": Hh * D,
            "K": int(lin["short_conv_kernel_size"]), "kda": len(lin["kda_layers"]), "mla": len(lin["full_attn_layers"]),
            "L": L, "dense": lead, "Id": int(config["intermediate_size"]), "I": int(config["moe_intermediate_size"]),
            "held": int(config["num_experts"]), "routed": int(config["routed_experts"]),
            "k": int(config["num_experts_per_token"]), "shared": int(config["num_shared_experts"]),
            "Hq": int(config["num_attention_heads"]), "Dn": int(config["qk_nope_head_dim"]),
            "Dr": int(config["qk_rope_head_dim"]), "Dv": int(config["v_head_dim"]), "rank": int(config["kv_lora_rank"]),
            "Q": int(dep["kda_block"]), "slots": int(dep["max_slots"]), "tokens": int(dep["mixed_step_tokens"]),
            "s": DTYPE_BYTES[dep.get("state_dtype", "float32")]}


def state_bytes_per_row_layer(config: dict) -> int:
    """A slot's matrix state in ONE KDA layer: 2,097,152 B at the published sizes."""
    s = sizes(config)
    return s["Hh"] * s["D"] * s["D"] * s["s"]


def block_flops(config: dict) -> int:
    """The matrix products of ONE visit of a packed block, every head."""
    s = sizes(config)
    Q, D = s["Q"], s["D"]
    return s["Hh"] * (3 * 2 * Q * D * D + 4 * Q * Q * D)


def block_bytes(config: dict) -> int:
    """What ONE visit moves: q, k, v and g in, o out, float32."""
    s = sizes(config)
    return 5 * s["Q"] * s["P"] * 4


def chunk_work(config: dict, records) -> tuple[float, float] | None:
    """(operations, bytes) of the chunk kernel over ``records``; None where a
    ``mixed`` record lacks ``kda_blocks`` or none walked a block."""
    mixed = [t for t in records or [] if t.get("kind") == "mixed"]
    if not mixed or any(t.get("kda_blocks") is None for t in mixed):
        return None
    blocks = sum(t["kda_blocks"] for t in mixed)
    return (blocks * block_flops(config), blocks * block_bytes(config)) if blocks else None


def update_bytes(config: dict, records) -> float | None:
    """The bytes the one-token update must move over ``records`` (``mixed`` and
    ``decode``); None where one lacks ``state_rows`` or no row was served."""
    steps = [t for t in records or [] if t.get("kind") in ("mixed", "decode")]
    if not steps or any(t.get("state_rows") is None or t.get("kda_blocks") is None for t in steps):
        return None
    rows = sum(t["state_rows"] for t in steps)
    return rows * sizes(config)["kda"] * 2 * state_bytes_per_row_layer(config) if rows else None


def token_flops(config: dict) -> int:
    """The weight products of ONE packed token through every layer, less the routed experts'."""
    s = sizes(config)
    H, P, r = s["H"], s["P"], s["D"]
    kda = 2 * H * 3 * P + 2 * P * H + 2 * 2 * (H * r + r * P) + 2 * H * s["Hh"]
    mla = (2 * H * s["Hq"] * (s["Dn"] + s["Dr"]) + 2 * H * (s["rank"] + s["Dr"])
           + 2 * s["Hq"] * s["rank"] * (s["Dn"] + s["Dv"]) + 2 * s["Hq"] * s["Dv"] * H)
    ffn = s["dense"] * 3 * 2 * H * s["Id"] + (s["L"] - s["dense"]) * s["shared"] * 3 * 2 * H * s["I"]
    return s["kda"] * kda + s["mla"] * mla + ffn


def mixed_step_flops(config: dict, record: dict) -> float | None:
    """The lower bound for the step a ``mixed`` record describes; None where
    the record lacks a counter."""
    need = ("kda_blocks", "state_rows", "attn_wide_tokens", "context_tokens", "batch", "expert_tokens_mean")
    if any(record.get(k) is None for k in need):
        return None
    s = sizes(config)
    tokens = record["attn_wide_tokens"] + record["state_rows"]
    held_pairs = record["expert_tokens_mean"] * s["routed"]          # (token, HELD expert) pairs a layer
    experts = (s["L"] - s["dense"]) * held_pairs * 3 * 2 * s["H"] * s["I"]
    latent = s["mla"] * 2 * s["Hq"] * (2 * s["rank"] + s["Dr"]) * record["context_tokens"]
    return (tokens * token_flops(config) + experts + record["kda_blocks"] * block_flops(config) + latent
            + record["batch"] * 2 * s["H"] * s["V"])


def _shape(*dims) -> str:
    return ",".join(str(d) for d in dims)


def update_pattern(config: dict) -> str:
    """The one-token update: its kernel by name (``kda_state_update_*``)."""
    return NOT_A_LOOP + rf"%?{UPDATE_KERNEL}\w*\b"


def conv_pattern(config: dict) -> str:
    """A result only the convolution has: the tails' store, its entry, a window,
    or the packed tokens' 3 P channels."""
    s = sizes(config)
    C, S, K = 3 * s["P"], s["slots"], s["K"]
    shapes = [_shape(s["kda"], S, K - 1, C), _shape(S, K - 1, C), _shape(S, K, C), rf"(?:1,)?(?:{s['tokens']}|{S}),{C}"]
    return NOT_A_LOOP + r"%?[\w.\-]+ = (?:f32|bf16)\[(?:" + "|".join(shapes) + r")\]"


def scan_pattern(config: dict) -> str:
    """Every state operation: the two kernels by name, the chunk kernel's padded
    operands, the convolution."""
    s = sizes(config)
    padded = -(-s["tokens"] // s["Q"]) * s["Q"]
    laid = rf"%?[\w.\-]+ = f32\[(?:{padded},{s['P']}|{padded},{s['Hh']},{s['D']}|{s['tokens']},{s['Hh']},{s['D']})\]"
    strip = lambda p: p[len(NOT_A_LOOP):]
    return (NOT_A_LOOP + rf"(?:%?{CHUNK_KERNEL}\b|{laid}|{strip(update_pattern(config))}|{strip(conv_pattern(config))})")
