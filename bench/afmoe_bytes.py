"""Bytes ONE mixed step of an ``afmoe`` configuration (Trinity-Mini's block) must
read, a LOWER bound, and the pages its windowed layers skip: what the
``swa_page_skip_share`` and ``afmoe_step_hbm_roofline_share`` readers share.
Sizes come from the configuration's file alone, never from the program; what a
step's rows chose and hold comes from the program's own counters on the step's
``/debug/timeline`` record.

An ``afmoe`` configuration (``model_type`` ``afmoe`` in its file) has
``num_dense_layers`` leading layers with a dense SwiGLU of ``intermediate_size``
and then expert layers with ``num_experts`` SwiGLU experts of
``moe_intermediate_size`` and ``num_shared_experts`` always-on ones; of its
``layer_types`` the ``sliding_attention`` layers read a window of a row's pages,
the ``full_attention`` layers all of them. A mixed step reads once, at the served
weight type:

- every layer's attention matrices: q, o and the output gate (hidden x heads x
  head size each), k and v (hidden x kv heads x head size each);
- the dense layers' three MLP matrices;
- in every expert layer the three matrices of each DISTINCT expert its rows chose
  (the record's ``experts_read``: a mean over the expert layers) and of the
  shared expert, and the router at the activation type;
- the head (hidden x vocabulary); the embedding's rows are left out;
- cached keys and values by page: ``window_pages_read`` (the record's count over
  the windowed layers) plus what the full layers hold, the record's
  ``context_pages`` / windowed layers x full layers; a page is page size x 2 x
  kv heads x head size x the cache type.

Scales, norms, the selection bias, activations, the keys and values a step
writes, and every re-read (a prompt chunk's queries walk their pages once a
tile of queries, not once a step) are left out, which makes a share a lower
bound: a reading over 100% is a fault of this count, never of the program.
Nothing where a record lacks a counter: no number of experts is ever assumed.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}
COUNTERS = ("experts_read", "window_pages_read", "context_pages")


def is_afmoe(config: dict) -> bool:
    return config.get("model_type") == "afmoe"


def sizes(config: dict) -> dict:
    dep = config["deployment_sizes"]
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    kinds = list(config["layer_types"])
    return {"H": int(config["hidden_size"]), "L": int(config["num_hidden_layers"]),
            "Hq": heads, "Hkv": kv_heads,
            "D": int(config.get("head_dim") or int(config["hidden_size"]) // heads),
            "V": int(config["vocab_size"]), "I_dense": int(config["intermediate_size"]),
            "I": int(config["moe_intermediate_size"]), "E": int(config["num_experts"]),
            "shared": int(config["num_shared_experts"]), "dense_layers": int(config["num_dense_layers"]),
            "windowed": kinds.count("sliding_attention"), "full": kinds.count("full_attention"),
            "page": int(dep["page_size"]), "w": DTYPE_BYTES[dep["weight_dtype"]],
            "a": DTYPE_BYTES[dep["activation_dtype"]], "c": DTYPE_BYTES[dep["kv_dtype"]]}


def expert_bytes(config: dict) -> int:
    """One expert's three matrices at the served weight type."""
    s = sizes(config)
    return 3 * s["H"] * s["I"] * s["w"]


def attention_bytes(config: dict) -> int:
    """One layer's attention matrices: q, o, the gate; k and v."""
    s = sizes(config)
    return (3 * s["H"] * s["Hq"] * s["D"] + 2 * s["H"] * s["Hkv"] * s["D"]) * s["w"]


def page_bytes(config: dict) -> int:
    """One page of ONE layer: keys and values."""
    s = sizes(config)
    return s["page"] * 2 * s["Hkv"] * s["D"] * s["c"]


def fixed_weight_bytes(config: dict) -> int:
    """What every step reads whatever its rows chose: attention of every layer,
    the dense layers' MLPs, the shared experts and routers of the expert layers,
    the head."""
    s = sizes(config)
    expert_layers = s["L"] - s["dense_layers"]
    return (s["L"] * attention_bytes(config) + s["dense_layers"] * 3 * s["H"] * s["I_dense"] * s["w"]
            + expert_layers * (s["shared"] * expert_bytes(config) + s["H"] * s["E"] * s["a"])
            + s["H"] * s["V"] * s["w"])


def counted(record: dict) -> bool:
    return all(record.get(k) is not None for k in COUNTERS)


def step_bytes(config: dict, record: dict) -> float | None:
    """The lower bound for the step a ``mixed`` record describes; None where
    the record lacks a counter."""
    if not counted(record):
        return None
    s = sizes(config)
    expert_layers = s["L"] - s["dense_layers"]
    full_pages = record["context_pages"] / s["windowed"] * s["full"] if s["windowed"] else 0.0
    return (fixed_weight_bytes(config) + record["experts_read"] * expert_layers * expert_bytes(config)
            + (record["window_pages_read"] + full_pages) * page_bytes(config))


def page_skip_share(records) -> float | None:
    """100 x (1 - window_pages_read / context_pages) over the ``decode`` and
    ``mixed`` records that carry both; None where none does."""
    recs = [t for t in records or [] if t.get("kind") in ("decode", "mixed") and t.get("context_pages")
            and t.get("window_pages_read") is not None]
    held = sum(t["context_pages"] for t in recs)
    return 100.0 * (1.0 - sum(t["window_pages_read"] for t in recs) / held) if held else None
