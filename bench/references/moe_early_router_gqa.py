"""Plain reference of the SmallThinker decoder block: a router placed before
attention, global layers with no position encoding beside windowed rotary
layers, and a sparse mixture of small ReGLU experts in place of the MLP.

Written from the model's published ``config.json`` (PowerInfer/
SmallThinker-21BA3B-Instruct) and the description of its family. Layer ``l``:

    h      = RMSNorm(x; attn_norm)
    r      = h @ router                    [T, E]   the router reads the
                                                    ATTENTION's normed input
    chosen = the moe_num_active_primary_experts largest of r, a token
    p      = softmax over those logits     (moe_primary_router_apply_softmax;
                                            norm_topk_prob changes nothing after it)
    q,k,v  = h @ wq, h @ wk, h @ wv        no bias
    rotary on q and k (theta rope_theta) iff rope_layout[l] == 1, else none
    a      = causal grouped-query attention, scale head_dim ** -0.5; iff
             sliding_window_layout[l] == 1 only keys with i - j < sliding_window_size
    x'     = x + a @ wo
    m      = RMSNorm(x'; moe_norm)
    y      = sum over the chosen e of p_e * (relu(m @ wg[e]) * (m @ wu[e])) @ wd[e]
    x_next = x' + y

then a final RMSNorm and an untied head. ``jax.numpy``, float32, matmul
precision "highest", one sequence, the whole context at once: no cache, no
kernels, no sorting, no dispatch tensors; every token reaches each of its
experts. Imports nothing from the program under test.

Departures from the source, each stated in the configuration's ``assumed``:
the router's input is ``h`` (``described_as``: "router placed before
attention"; the config has no key for it); no q/k/v bias; ReGLU's gate is
``relu``; nothing of the "secondary experts" the family's description
mentions, for which the config has no key. An expert a token did not choose
gets the weight 0 for it, so running every expert over every token and summing
is the chosen experts' weighted sum, with static shapes.

Weights are data, as in ``dense_gqa.py``: ``layer(i)`` hands over layer i's
leaves in float32, matrices ``[in, out]``, the experts' stacked ``[E, in,
out]``.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location("bench_reference_dense_gqa",
                                               Path(__file__).with_name("dense_gqa.py"))
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)

LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "moe_norm", "router", "wg", "wu", "wd")
OPTIONAL_LEAVES = ()


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size", "rope_theta", "rms_norm_eps", "moe_ffn_hidden_size",
        "moe_num_primary_experts", "moe_num_active_primary_experts", "sliding_window_size")}
    for layout in ("rope_layout", "sliding_window_layout"):
        out[layout] = tuple(model[layout])
        if len(out[layout]) != out["num_hidden_layers"]:
            raise ValueError(f"{layout} has {len(out[layout])} entries for {out['num_hidden_layers']} layers")
    if not model.get("moe_primary_router_apply_softmax", True):
        raise ValueError("this reference is the softmax-over-the-chosen router")
    return out


def experts(m, gate, idx, w):
    """The mixture for tokens m [T, H] under routing weights gate [T, k] of
    the experts idx [T, k]: one expert at a time over every token."""
    def one(out, e):
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)            # [T]
        y = (jax.nn.relu(m @ w["wg"][e]) * (m @ w["wu"][e])) @ w["wd"][e]
        return out + weight[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(w["wg"].shape[0]))
    return out


def block(x, w, positions, sizes, rope, windowed):
    """One decoder layer. ``w``: float32 matrices [in, out] and vectors;
    ``rope`` and ``windowed`` are this layer's entries of the two layouts."""
    Hq, Hkv, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    T = x.shape[0]
    h = dense.rms_norm(x, w["attn_norm"], sizes["rms_norm_eps"])
    chosen, idx = jax.lax.top_k(h @ w["router"], sizes["moe_num_active_primary_experts"])
    gate = jax.nn.softmax(chosen, axis=-1)
    q = (h @ w["wq"]).reshape(T, Hq, D)
    k = (h @ w["wk"]).reshape(T, Hkv, D)
    if rope:
        q = dense.rotary(q, positions, sizes["rope_theta"])
        k = dense.rotary(k, positions, sizes["rope_theta"])
    a = dense.attention(q, k, (h @ w["wv"]).reshape(T, Hkv, D),
                        sizes["sliding_window_size"] if windowed else None)
    x = x + a.reshape(T, Hq * D) @ w["wo"]
    return x + experts(dense.rms_norm(x, w["moe_norm"], sizes["rms_norm_eps"]), gate, idx, w)


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(token_ids))
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        step = jax.jit(lambda x, w, rope, windowed: block(x, w, positions, sizes, rope, windowed),
                       static_argnums=(2, 3))
        for i in range(sizes["num_hidden_layers"]):
            x = step(x, layer(i), bool(sizes["rope_layout"][i]), bool(sizes["sliding_window_layout"][i]))
        x = dense.rms_norm(x, final_norm, sizes["rms_norm_eps"])
        return jax.jit(lambda x, head: x @ head)(x, head)
