"""Plain reference of the Trinity-Mini decoder block (``model_type`` ``afmoe``):
leading dense layers before expert layers, a sigmoid router with a selection
bias and an always-on shared expert, gated grouped-query attention with q/k
norms, four norms a layer, windowed rotary layers beside global layers with no
position encoding.

Written from the model's published ``config.json`` (arcee-ai/Trinity-Mini) and,
for what the config has no key for (marked +, each in the configuration's
``assumed``), the family's published modelling code. T tokens, hidden H:

    x       = E[tok] * sqrt(H)                                        (mup_enabled)
  layer l:
    a       = RMSNorm(x; attn_norm)
    q,k,v,g = a @ wq [Hq, D], a @ wk [Hkv, D], a @ wv [Hkv, D], a @ attn_gate [Hq * D]
    q, k    = RMSNorm over D (q_norm, k_norm) +
    q, k    = rotate-half rotary (rope_theta) where layer_types[l] is
              sliding_attention; none where it is full_attention +
    o       = softmax(q k^T / sqrt(D) + mask) v, causal; in a sliding layer only
              keys with query_pos - key_pos < sliding_window                (GQA)
    o       = o * sigmoid(g) +
    x       = x + RMSNorm(o @ wo; post_attn_norm) +
    m       = RMSNorm(x; mlp_norm | moe_norm)
    f       = (silu(m @ wg) * (m @ wu)) @ wd          width intermediate_size, l < num_dense_layers
    f       = shared(m) + sum over the chosen e of w_e * expert_e(m)     else, each a SwiGLU
              of width moe_intermediate_size (the shared one num_shared_experts times that):
              s = sigmoid(m @ router) over num_experts; the chosen: the num_experts_per_tok
              largest of s + router_bias +;  w = s[chosen] (no bias);  w = w / (sum(w) + 1e-20)
              (route_norm);  w = w * route_scale;  n_group = topk_group = 1: no grouping
    x       = x + RMSNorm(f; post_ffn_norm) +

then a final RMSNorm and an untied head. ``jax.numpy``, float32, matmul
precision "highest", one sequence, the whole context at once: no cache, no
kernels, no pages. Imports nothing from the program under test.

The program keeps the two kinds of layer as two stacks in ``params["layers"]``:
the expert layers under the mixtures' names (leading axis num_hidden_layers -
num_dense_layers), the leading dense layers under ``dense_*`` names of their
own (leading axis num_dense_layers). ``layer(i)`` slices EVERY declared leaf at
index i (past a stack's end the index is clamped): this file asks ``layer(l)``
for dense layer l and ``layer(l - num_dense_layers)`` for an expert layer, and
reads only the stack it asked for.

The five assumed mechanisms are switches of ``sizes`` (``assumed_mechanisms`` in
the configuration's file, all as the modelling code has them where absent), so
that a control can run this file with one of them changed and show that the
comparison deciding ``correct`` sees it (``hack/trinity_correct_control.py``).

Departures, for memory alone: attention runs over blocks of ``QUERY_BLOCK``
queries (every query still sees every key its mask allows); the logits' rows
are computed as they are read (``moe_dsa_gqa._Logits``: 9,000 x 200,192 float32
is 7.2 GB); ``forward`` waits for each layer before it asks for the next one's
weights, so the device never holds two layers' float32 views (3.4 GB each at
the published widths). An expert a token did not choose gets the weight 0 for
it, so running every expert over every token and summing is the chosen
experts' weighted sum.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location("bench_reference_moe_dsa_gqa",
                                               Path(__file__).with_name("moe_dsa_gqa.py"))
sparse = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sparse)
dense = sparse.dense

ATTENTION = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "attn_gate", "post_attn_norm", "post_ffn_norm")
DENSE_LEAVES = tuple("dense_" + n for n in ATTENTION + ("mlp_norm", "wg", "wu", "wd"))
EXPERT_LEAVES = ATTENTION + ("moe_norm", "router", "router_bias", "wg", "wu", "wd",
                             "shared_wg", "shared_wu", "shared_wd")
LEAVES = EXPERT_LEAVES + DENSE_LEAVES
OPTIONAL_LEAVES = ()
QUERY_BLOCK = 256
MECHANISMS = {"attention_output_gate": True, "qk_norm": True, "post_norms": True,
              "selection_bias": "selection", "global_layers_rope": False}


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "rope_theta", "rms_norm_eps", "intermediate_size", "moe_intermediate_size",
        "num_experts", "num_experts_per_tok", "num_dense_layers", "num_shared_experts", "sliding_window",
        "route_scale")}
    out["layer_types"] = tuple(model["layer_types"])
    if len(out["layer_types"]) != out["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(out['layer_types'])} entries for {out['num_hidden_layers']} layers")
    if set(out["layer_types"]) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types {sorted(set(out['layer_types']))}: sliding_attention or full_attention")
    if model.get("score_func") != "sigmoid" or not model.get("route_norm") or not model.get("mup_enabled"):
        raise ValueError("this reference is the sigmoid router with normalised weights under a scaled embedding")
    if model.get("n_group", 1) != 1 or model.get("topk_group", 1) != 1:
        raise ValueError("this reference routes over one group")
    chosen = {**MECHANISMS, **(model.get("assumed_mechanisms") or {})}
    if set(chosen) - set(MECHANISMS) or chosen["selection_bias"] not in ("selection", "none", "weights"):
        raise ValueError(f"assumed_mechanisms {chosen}: the switches are {MECHANISMS}")
    out.update(chosen)
    return out


def attention(q, k, v, window):
    """``dense_gqa.attention`` a block of ``QUERY_BLOCK`` queries at a time:
    q [T, Hq, D], k and v [T, Hkv, D]; with a window w, position t sees
    positions t-w+1 .. t."""
    T, Hq, D = q.shape
    group = Hq // k.shape[1]
    kk, vv = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    n = -(-T // QUERY_BLOCK)
    key_pos = jnp.arange(T)

    def one(args):
        qb, pos = args
        scores = jnp.einsum("qhd,shd->hqs", qb, kk) / jnp.sqrt(jnp.float32(D))
        mask = key_pos[None, :] <= pos[:, None]
        if window:
            mask = mask & (key_pos[None, :] > pos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, vv)

    # Padded queries sit at position T - 1: they see keys, so no row is empty.
    pos = jnp.minimum(jnp.arange(n * QUERY_BLOCK), T - 1).reshape(n, QUERY_BLOCK)
    qb = jnp.pad(q, ((0, n * QUERY_BLOCK - T), (0, 0), (0, 0))).reshape(n, QUERY_BLOCK, Hq, D)
    return jax.lax.map(one, (qb, pos)).reshape(n * QUERY_BLOCK, Hq, D)[:T]


def route(m, w, sizes):
    """Routing weights [T, k] and the chosen experts [T, k] for tokens m [T, H]."""
    scores = jax.nn.sigmoid(m @ w["router"])
    select = scores if sizes["selection_bias"] == "none" else scores + w["router_bias"]
    _, idx = jax.lax.top_k(select, sizes["num_experts_per_tok"])
    gate = jnp.take_along_axis(select if sizes["selection_bias"] == "weights" else scores, idx, axis=-1)
    return gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) * sizes["route_scale"], idx


def swiglu(m, wg, wu, wd):
    return (jax.nn.silu(m @ wg) * (m @ wu)) @ wd


def block(x, w, positions, sizes, sliding, is_dense):
    """One decoder layer. ``w``: float32 matrices [in, out] and vectors, under
    the expert layers' names (a dense layer's with ``dense_`` taken off)."""
    Hq, Hkv, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    T, eps = x.shape[0], sizes["rms_norm_eps"]
    post = (lambda y, gain: dense.rms_norm(y, gain, eps)) if sizes["post_norms"] else (lambda y, gain: y)
    a = dense.rms_norm(x, w["attn_norm"], eps)
    q, k = (a @ w["wq"]).reshape(T, Hq, D), (a @ w["wk"]).reshape(T, Hkv, D)
    if sizes["qk_norm"]:
        q, k = dense.rms_norm(q, w["q_norm"], eps), dense.rms_norm(k, w["k_norm"], eps)
    if sliding or sizes["global_layers_rope"]:
        q, k = dense.rotary(q, positions, sizes["rope_theta"]), dense.rotary(k, positions, sizes["rope_theta"])
    o = attention(q, k, (a @ w["wv"]).reshape(T, Hkv, D), sizes["sliding_window"] if sliding else None)
    o = o.reshape(T, Hq * D)
    if sizes["attention_output_gate"]:
        o = o * jax.nn.sigmoid(a @ w["attn_gate"])
    x = x + post(o @ w["wo"], w["post_attn_norm"])
    if is_dense:
        f = swiglu(dense.rms_norm(x, w["mlp_norm"], eps), w["wg"], w["wu"], w["wd"])
    else:
        m = dense.rms_norm(x, w["moe_norm"], eps)
        f = sparse.experts(m, *route(m, w, sizes), w)
        if sizes["num_shared_experts"]:
            f = f + swiglu(m, w["shared_wg"], w["shared_wu"], w["shared_wd"])
    return x + post(f, w["post_ffn_norm"])


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``,
    the rows computed as they are read."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(token_ids))
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32) * jnp.sqrt(jnp.float32(sizes["hidden_size"]))
        step = jax.jit(lambda x, w, sliding, is_dense: block(x, w, positions, sizes, sliding, is_dense),
                       static_argnums=(2, 3))
        lead = sizes["num_dense_layers"]
        for i, kind in enumerate(sizes["layer_types"]):
            if i < lead:
                w = {n[len("dense_"):]: a for n, a in layer(i).items() if n in DENSE_LEAVES}
            else:
                w = {n: a for n, a in layer(i - lead).items() if n in EXPERT_LEAVES}
            x = jax.block_until_ready(step(x, w, kind == "sliding_attention", i < lead))
            del w
        return sparse._Logits(dense.rms_norm(x, final_norm, sizes["rms_norm_eps"]), head)
