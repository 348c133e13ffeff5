"""Operations and bytes of a ``jamba`` configuration's selective scan and of its
mixed step, each a LOWER bound, and where the state-space operations' device
time is found in a trace: what the ``selscan_*`` readers and
``jamba2_mixed_step_mfu`` share. Sizes come from the configuration's file alone,
never from the program; what a step's rows were comes from the program's own
counters on the step's ``/debug/timeline`` record.

A ``jamba`` configuration (``model_type`` in its file) has ``num_hidden_layers``
layers, an attention layer where ``(l - attn_layer_offset) % attn_layer_period ==
0`` and else a Mamba-1 mixer (Di = ``mamba_expand`` x H channels with a float32
state of ``mamba_d_state`` N columns a channel, a convolution of ``mamba_d_conv``
taps over Di channels, a bottleneck of ``mamba_dt_rank`` R for Delta), and in
EVERY layer a dense gated MLP of ``intermediate_size``; a tied head over
``vocab_size``.

**The chunk kernel** (``ssm_scan_chunk_kernel``), a token of a row of more than
one token in ONE Mamba layer: Delta and c in and y out (Di float32 each) and B's
and C's N values: (3 Di + 2 N) x 4 = 61,568 B at the published sizes; a row's
state once in and once out (2 x N x Di x 4 = 655,360 B a row a layer). That the
kernel is HANDED B and C as columns replicated over 128 lanes (16,384 B a token
more) is how it is built, not work it has to do: it counts in the kernel's time,
not here, so forming the columns in VMEM would raise the share. ``ssm_chunk_tokens``
and ``ssm_chunk_rows`` of a ``mixed`` record are the tokens and the rows over all
Mamba layers. Its operations, a token a layer: Di x N state entries, each an
exponential, three multiply-adds and a term of the sum over N: 9 x Di x N. They
are the vector unit's and the transcendental unit's, not the matrix unit's: over
the chip's bf16 peak they are 20 times less time than the bytes, so the bound is
the bytes', and the kernel's true ceiling (about 17 vector operations a token a
128 channels at four a cycle: ``hack/ssm_scan_bench.py``) lies ABOVE it: a share
of 25% of the bytes' bound is 60% of the vector unit's (my chip run, PR 64).

**The one-token update**: a row's state read and written in every Mamba layer, 2
x N x Di x 4 B a row a layer (``state_rows`` of a ``mixed`` record: its one-token
rows; of a ``decode`` record: its live rows summed over the steps it ran).

**A mixed step's matrix products** (``jamba2_mixed_step_mfu``), a packed token: a
Mamba layer's four 2 H (2 Di) + 2 Di (R + 2 N) + 2 R Di + 2 Di H, an attention
layer's four 2 H (2 Hq D + 2 Hkv D), every layer's MLP 3 x 2 H I; attention over
the read keys 4 Hq D a (query, key) with every row counted as ONE query over its
context (``context_tokens``: a chunk's other queries are left out); the head 2 H
V for each of the step's rows (``batch``). The scan, the convolution, the norms
and the gate are vector work and left out. The packed tokens of a record are its
``attn_wide_tokens`` (the rows of more than one token) and its ``state_rows``
(the one-token rows).

Every count leaves something out, which makes a share a lower bound: a reading
over 100% is a fault of this count, never of the program. Nothing where a record
lacks a counter (the parent commit).

The state-space operations' device time (``selscan_busy_share``) is read off the
trace file by ``mla_bytes.op_times`` (a child, ``hybrid_bytes.read_file``) with
``scan_pattern``: what ``hybrid_bytes.ssm_pattern`` finds for the other Mamba-1
model, ``ssm_busy_share``'s, so that the two cells' readings compare: a kernel
named ``ssm_*`` (the chunk kernel and the one-token kernel), or an operation that
holds a layer's states ``f32[slots,N,Di]``, one state ``f32[N,Di]``, the
convolution's tails ``f32[slots,K-1,Di]`` or its window ``f32[slots,K,Di]`` (this
store lies channels in the lanes). The stacked store of all layers does not
match; the mixers' other elementwise work (the gate, the softplus, the inner
norms) is any ``[tokens, Di]`` array's and is not seen: a lower bound.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
CHUNK_KERNEL = "ssm_scan_chunk_kernel"


def is_jamba(config: dict) -> bool:
    return config.get("model_type") == "jamba"


def sizes(config: dict) -> dict:
    dep = config["deployment_sizes"]
    H, heads, L = int(config["hidden_size"]), int(config["num_attention_heads"]), int(config["num_hidden_layers"])
    offset, period = int(config["attn_layer_offset"]), int(config["attn_layer_period"])
    attention = sum((l - offset) % period == 0 for l in range(L))
    return {"H": H, "Hq": heads, "Hkv": int(config["num_key_value_heads"]), "D": int(config.get("head_dim") or H // heads),
            "V": int(config["vocab_size"]), "I": int(config["intermediate_size"]), "Di": int(config["mamba_expand"]) * H,
            "N": int(config["mamba_d_state"]), "K": int(config["mamba_d_conv"]), "R": int(config["mamba_dt_rank"]),
            "mamba": L - attention, "attention": attention, "L": L, "Q": int(dep["ssm_chunk_tokens"]),
            "slots": int(dep["max_slots"]), "tokens": int(dep["mixed_step_tokens"]),
            "s": DTYPE_BYTES[dep.get("state_dtype", "float32")]}


def state_bytes_per_row_layer(config: dict) -> int:
    """A slot's matrix state in ONE Mamba layer: 327,680 B at the published sizes."""
    s = sizes(config)
    return s["N"] * s["Di"] * s["s"]


def token_bytes(config: dict) -> int:
    """What the chunk kernel has to move for ONE token of ONE layer: Delta, c, y, B and C."""
    s = sizes(config)
    return (3 * s["Di"] + 2 * s["N"]) * 4


def token_ops(config: dict) -> int:
    """The chunk kernel's operations for ONE token of ONE layer."""
    s = sizes(config)
    return 9 * s["Di"] * s["N"]


def chunk_work(config: dict, records) -> tuple[float, float] | None:
    """(operations, bytes) of the chunk kernel over ``records``; None where a
    ``mixed`` record lacks a counter or none walked a token."""
    mixed = [t for t in records or [] if t.get("kind") == "mixed"]
    if not mixed or any(t.get("ssm_chunk_tokens") is None or t.get("ssm_chunk_rows") is None for t in mixed):
        return None
    tokens, rows = sum(t["ssm_chunk_tokens"] for t in mixed), sum(t["ssm_chunk_rows"] for t in mixed)
    if not tokens:
        return None
    return tokens * token_ops(config), tokens * token_bytes(config) + rows * 2 * state_bytes_per_row_layer(config)


def token_flops(config: dict) -> int:
    """The weight products of ONE packed token through every layer."""
    s = sizes(config)
    H, Di = s["H"], s["Di"]
    mixer = 2 * H * 2 * Di + 2 * Di * (s["R"] + 2 * s["N"]) + 2 * s["R"] * Di + 2 * Di * H
    attention = 2 * H * (2 * s["Hq"] * s["D"] + 2 * s["Hkv"] * s["D"])
    return s["mamba"] * mixer + s["attention"] * attention + s["L"] * 3 * 2 * H * s["I"]


def mixed_step_flops(config: dict, record: dict) -> float | None:
    """The lower bound for the step a ``mixed`` record describes; None where the
    record lacks a counter."""
    need = ("ssm_chunk_tokens", "state_rows", "attn_wide_tokens", "context_tokens", "batch")
    if any(record.get(k) is None for k in need):
        return None
    s = sizes(config)
    tokens = record["attn_wide_tokens"] + record["state_rows"]
    return (tokens * token_flops(config) + s["attention"] * 4 * s["Hq"] * s["D"] * record["context_tokens"]
            + record["batch"] * 2 * s["H"] * s["V"])


def scan_pattern(config: dict) -> str:
    """What an operation of the convolution or the scan holds in its HLO text:
    ``hybrid_bytes.ssm_pattern``'s form, the state laid (N, Di)."""
    s = sizes(config)
    S, N, Di, K = s["slots"], s["N"], s["Di"], s["K"]
    shapes = [(S, N, Di), (N, Di), (S, K - 1, Di), (S, K, Di)]
    return r"^%?ssm_\w+|f32\[(" + "|".join(",".join(map(str, sh)) for sh in shapes) + r")\]"
