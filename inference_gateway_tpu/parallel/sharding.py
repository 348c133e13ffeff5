"""Sharding rules: map model pytrees onto the mesh.

Megatron-style tensor parallel layout for the Llama pytree
(models/llama.py): QKV and gate/up projections are column-sharded on
``tp``; the output and down projections are row-sharded on the
contraction axis so XLA inserts a single ``psum`` (reduce-scatter when
profitable) per block. Embedding/LM head are vocab-sharded. KV caches
shard heads on ``tp`` and batch on ``dp``. XLA's SPMD partitioner derives
every collective from these annotations — nothing is hand-scheduled.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from inference_gateway_tpu.models.llama import LlamaConfig


def llama_param_specs(cfg: LlamaConfig) -> dict:
    """PartitionSpec pytree matching init_params' structure."""
    specs = {
        "embed": P("tp", None),  # vocab-sharded
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(None, None),
            "wg": P(None, None, "tp"),
            "wu": P(None, None, "tp"),
            "wd": P(None, "tp", None),
        },
        "final_norm": P(None),
    }
    if cfg.qkv_bias:
        specs["layers"]["bq"] = P(None, "tp")
        specs["layers"]["bk"] = P(None, "tp")
        specs["layers"]["bv"] = P(None, "tp")
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def quantized_specs(specs: dict, mode: str = "int8") -> dict:
    """Spec tree for a quantized pytree (ops/quant.py): each quantizable
    weight's P becomes a QTensor/Q4Tensor node of (q_spec, scale_spec).

    int8: the scale keeps the weight's layout except the contraction
    (-2) axis, which is size 1 and must stay unsharded. int4: the packed
    q keeps the weight's spec verbatim (packing halves the contraction
    axis but not its sharding), and the scale's group axis inherits the
    contraction axis's placement ((..., G, 1, out) — a tp shard of the
    input dimension owns the matching shard of groups)."""
    from inference_gateway_tpu.ops.quant import QUANTIZABLE, Q4Tensor, QTensor

    def qspec(p: P):
        parts = tuple(p)
        if mode == "int4":
            scale = parts[:-2] + (parts[-2], None) + parts[-1:]
            return Q4Tensor(p, P(*scale))
        scale = parts[:-2] + (None,) + parts[-1:]
        return QTensor(p, P(*scale))

    out = dict(specs)
    layers = dict(specs["layers"])
    for name in QUANTIZABLE:
        if name in layers:
            layers[name] = qspec(layers[name])
    out["layers"] = layers
    if "lm_head" in out:
        out["lm_head"] = qspec(out["lm_head"])
    return out


def pp_layer_specs(cfg: LlamaConfig, quantized: str | None = None) -> dict:
    """Spec tree for params["layers"] with the LAYER axis sharded over
    ``pp`` on top of the Megatron tp layout — the stage-sharded layout
    models/llama.py::forward_pp consumes via shard_map (each device gets
    its (L/pp, .../tp) block). ``quantized`` wraps quantizable leaves in
    QTensor/Q4Tensor spec nodes exactly like quantized_specs."""
    base = llama_param_specs(cfg)
    if quantized:
        base = quantized_specs(base, mode=quantized)

    from inference_gateway_tpu.ops.quant import Q4Tensor, QTensor

    def add_pp(p):
        return P("pp", *tuple(p)[1:])

    def walk(node):
        if isinstance(node, (QTensor, Q4Tensor)):
            return type(node)(add_pp(node.q), add_pp(node.scale))
        return add_pp(node)

    return {
        name: walk(spec) for name, spec in base["layers"].items()
    }


def pp_param_specs(cfg: LlamaConfig, quantized: str | None = None) -> dict:
    """Full-tree specs for pp×tp serving: layers stage-sharded (above),
    embed/lm_head/norms as in the tp-only layout (pp-replicated)."""
    base = llama_param_specs(cfg)
    if quantized:
        base = quantized_specs(base, mode=quantized)
    out = dict(base)
    out["layers"] = pp_layer_specs(cfg, quantized=quantized)
    return out


def llama_cache_specs() -> dict:
    """KV cache (L, B, S, Hkv, D): batch on dp, kv heads on tp."""
    return {"k": P(None, "dp", None, "tp", None), "v": P(None, "dp", None, "tp", None)}


def batch_spec() -> P:
    """Activations/token batches: (B, T, ...) → B on dp, T on sp."""
    return P("dp", "sp")


def named(mesh: Mesh, spec_tree):
    """PartitionSpec pytree → NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params, mesh: Mesh, specs, donate: bool = False) -> dict:
    """Device-put an existing pytree onto the mesh per the spec tree.

    ``donate``: the tree is the caller's to give up (a seeded build).
    Each leaf is then laid out, waited for and freed before the next, so
    the device that built the tree holds it plus one leaf's shards at
    most, never the tree twice: SmallThinker's 7.5 GB stage ran device 0
    of a four-chip host out of memory laid out in one call (my chip run,
    PR 28). The build itself still starts on one device (ROADMAP D14)."""
    shardings = named(mesh, specs)
    if not donate:
        return jax.device_put(params, shardings)
    return jax.tree.map(lambda leaf, s: jax.block_until_ready(jax.device_put(leaf, s, donate=True)),
                        params, shardings)


def check_divisibility(cfg: LlamaConfig, mesh: Mesh) -> None:
    """Fail fast when the model doesn't tile onto the mesh."""
    tp = mesh.shape.get("tp", 1)
    for name, dim in (
        ("num_heads", cfg.num_heads),
        ("num_kv_heads", cfg.num_kv_heads),
        ("intermediate_size", cfg.intermediate_size),
        ("vocab_size", cfg.vocab_size),
    ):
        if dim % tp != 0:
            raise ValueError(f"{name}={dim} not divisible by tp={tp}")
