"""Operations and bytes the algorithm needs, from a configuration file's sizes.

Read from the file under ``bench/configs``, never from the program, so a
change to the program cannot move the yardstick. The dense block both
configurations share: q/k/v/o projections, a gated MLP, an untied output head.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def sizes(config: dict) -> dict:
    H = int(config["hidden_size"])
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    D = int(config.get("head_dim") or H // heads)
    return {"H": H, "I": int(config["intermediate_size"]), "L": int(config["num_hidden_layers"]),
            "V": int(config["vocab_size"]), "Hq": heads, "Hkv": kv_heads, "D": D}


def layer_matmul_params(config: dict) -> int:
    """Weights of one layer's seven matrices."""
    s = sizes(config)
    attn = s["H"] * s["Hq"] * s["D"] * 2 + s["H"] * s["Hkv"] * s["D"] * 2
    return attn + 3 * s["H"] * s["I"]


def head_params(config: dict) -> int:
    s = sizes(config)
    return s["H"] * s["V"]


def weight_bytes_per_step(config: dict) -> int:
    """Bytes of weights one decode step must read: every layer's matrices and the
    output head at the served weight type, once. The embedding table is read a
    row per token and the scales and norms are under 0.1%: both left out, which
    makes the share a lower bound."""
    wbytes = DTYPE_BYTES[config["deployment_sizes"]["weight_dtype"]]
    s = sizes(config)
    return (s["L"] * layer_matmul_params(config) + head_params(config)) * wbytes


def kv_bytes_per_token(config: dict) -> int:
    """Keys and values of one token over all layers, at the cache's type."""
    s = sizes(config)
    return 2 * s["L"] * s["Hkv"] * s["D"] * DTYPE_BYTES[config["deployment_sizes"]["kv_dtype"]]


def flops_per_token(config: dict, context: int = 0) -> float:
    """Multiply-adds x 2 for one token through the layers and the head, plus
    attention over ``context`` cached tokens."""
    s = sizes(config)
    dense = 2.0 * (s["L"] * layer_matmul_params(config) + head_params(config))
    attn = 4.0 * s["L"] * s["Hq"] * s["D"] * context
    return dense + attn
