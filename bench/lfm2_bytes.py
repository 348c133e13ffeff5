"""Bytes ONE decode step of an ``lfm2_moe`` configuration (LFM2's mixture block)
must read, a LOWER bound, and where its gated short convolutions are in a device
trace: what the ``lfm2_decode_hbm_roofline_share`` and ``shortconv_busy_share``
readers share. Sizes come from the configuration's file alone, never from the
program; what a step's rows chose and hold comes from the program's own
counters on the chunk's ``decode`` record of ``/debug/timeline``.

An ``lfm2_moe`` configuration (``model_type`` in its file) has, by
``layer_types``, ``conv`` layers (W_in hidden x 3 hidden, W_out hidden x hidden,
and a slot's last ``conv_L_cache`` - 1 values of hidden numbers as its state) and
``full_attention`` layers (q and o hidden x heads x head size, k and v hidden x kv
heads x head size; the only layers that cache keys and values);
``num_dense_layers`` leading layers with a dense SwiGLU of ``intermediate_size``,
then expert layers with ``num_experts`` SwiGLU experts of
``moe_intermediate_size`` and no shared expert; a tied head. A decode step reads
once, at the served weight type:

- every operator's matrices, the dense layers' three MLP matrices, every expert
  layer's router at the activation type, and the head AS HELD: the tied head is
  the embedding, hidden x vocabulary at the activation type (the program keeps no
  int8 copy of it);
- in every expert layer the three matrices of each DISTINCT expert its rows chose
  (the record's ``experts_read``: a mean over the chunk's steps and the layers);
- cached keys and values by page: the record's ``context_pages`` (the pages its
  rows hold, summed over the caching layers and the chunk's ``steps``) over
  ``steps``; a page is page size x 2 x kv heads x head size x the cache type;
- each row's state, read and written: rows (the record's ``batch``) x conv layers
  x (taps - 1) x hidden x the state's type, twice.

Scales, norms, taps, the selection bias, activations, the keys and values a step
writes and the embedding's rows are left out, which makes a share a lower bound:
a reading over 100% is a fault of this count, never of the program. Nothing where
a record lacks a counter: no number of experts is ever assumed.
"""

from __future__ import annotations

import json
from pathlib import Path

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
COUNTERS = ("experts_read", "context_pages", "batch", "steps")


def is_lfm2(config: dict) -> bool:
    return config.get("model_type") == "lfm2_moe"


def sizes(config: dict) -> dict:
    dep = config["deployment_sizes"]
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    kinds = list(config["layer_types"])
    return {"H": int(config["hidden_size"]), "L": int(config["num_hidden_layers"]), "Hq": heads, "Hkv": kv_heads,
            "D": int(config.get("head_dim") or int(config["hidden_size"]) // heads), "V": int(config["vocab_size"]),
            "I_dense": int(config["intermediate_size"]), "I": int(config["moe_intermediate_size"]),
            "E": int(config["num_experts"]), "dense_layers": int(config["num_dense_layers"]),
            "conv": kinds.count("conv"), "full": kinds.count("full_attention"), "K": int(config["conv_L_cache"]),
            "slots": int(dep["max_slots"]), "page": int(dep["page_size"]), "w": DTYPE_BYTES[dep["weight_dtype"]],
            "a": DTYPE_BYTES[dep["activation_dtype"]], "c": DTYPE_BYTES[dep["kv_dtype"]],
            "s": DTYPE_BYTES[dep["state_dtype"]]}


def conv_bytes(config: dict) -> int:
    """One gated short convolution's two matrices: W_in and W_out."""
    s = sizes(config)
    return 4 * s["H"] * s["H"] * s["w"]


def attention_bytes(config: dict) -> int:
    """One attention operator's matrices: q and o; k and v."""
    s = sizes(config)
    return (2 * s["H"] * s["Hq"] * s["D"] + 2 * s["H"] * s["Hkv"] * s["D"]) * s["w"]


def expert_bytes(config: dict) -> int:
    """One expert's three matrices at the served weight type."""
    s = sizes(config)
    return 3 * s["H"] * s["I"] * s["w"]


def page_bytes(config: dict) -> int:
    """One page of ONE caching layer: keys and values."""
    s = sizes(config)
    return s["page"] * 2 * s["Hkv"] * s["D"] * s["c"]


def state_bytes_per_row(config: dict) -> int:
    """What one row keeps in the state store, over the conv layers."""
    s = sizes(config)
    return s["conv"] * (s["K"] - 1) * s["H"] * s["s"]


def fixed_weight_bytes(config: dict) -> int:
    """What every step reads whatever its rows chose: the operators of every
    layer, the dense layers' MLPs, the routers, the tied head as held."""
    s = sizes(config)
    return (s["conv"] * conv_bytes(config) + s["full"] * attention_bytes(config)
            + s["dense_layers"] * 3 * s["H"] * s["I_dense"] * s["w"]
            + (s["L"] - s["dense_layers"]) * s["H"] * s["E"] * s["a"] + s["H"] * s["V"] * s["a"])


def counted(record: dict) -> bool:
    return all(record.get(k) for k in COUNTERS)


def decode_step_bytes(config: dict, record: dict) -> float | None:
    """The lower bound for ONE step of the chunk a ``decode`` record describes;
    None where the record lacks a counter."""
    if not counted(record):
        return None
    s = sizes(config)
    return (fixed_weight_bytes(config)
            + record["experts_read"] * (s["L"] - s["dense_layers"]) * expert_bytes(config)
            + record["context_pages"] / record["steps"] * page_bytes(config)
            + record["batch"] * 2 * state_bytes_per_row(config))


def shortconv_pattern(config: dict) -> str:
    """What an operation of the gated short convolution holds in its HLO text:
    the input projection's 3 x hidden columns, or the state store's own shapes
    (a layer's entry, the entry with the new input, the whole store); a Pallas
    kernel of it would be named ``shortconv_*``. Never a loop or a branch, whose
    event spans everything inside it and whose text names every array it
    carries. W_out has the shape of the attention's q and o projections and is
    NOT counted: a lower bound."""
    s = sizes(config)
    tail, store = (s["slots"], s["K"] - 1, s["H"]), (s["conv"], s["slots"], s["K"] - 1, s["H"])
    shapes = [tail, (s["slots"], s["K"], s["H"]), store, (1,) + tail]
    return (r"^(?!%?(?:while|cond|conditional|call)[\w.\-]* = )(?:%?shortconv_\w+|.*(?:\[(?:\d+,)*" + str(3 * s["H"])
            + r"\]|f32\[(?:" + "|".join(",".join(map(str, sh)) for sh in shapes) + r")\]))")


def shortconv_times(trace: dict, config: dict) -> dict | None:
    """Seconds the convolution's operations ran (union of their intervals on a
    device's operation line) and how many events, read from the trace the run
    reduced (its ``file``) in a child, as ``hybrid_bytes.ssm_times`` reads the
    state store's; left beside the trace as ``<file>.shortconv.json``. None for
    another configuration, or where the trace holds no such operation: a
    program without the layer. A hand-built trace brings its own ``shortconv_ops``."""
    if not is_lfm2(config):
        return None
    times = trace.get("shortconv_ops")
    if times is None and trace.get("file"):
        from hybrid_bytes import _read_in_child

        found = _read_in_child(trace["file"], shortconv_pattern(config))
        times = {"shortconv_s": found["ssm_s"], "shortconv_events": found["ssm_events"],
                 "pattern": shortconv_pattern(config)}
        Path(trace["file"] + ".shortconv.json").write_text(json.dumps(times))
    return times if times and times["shortconv_events"] > 0 else None
