"""Plain reference of the LFM2 mixture decoder (``model_type`` ``lfm2_moe``):
gated short convolutions beside a few grouped-query attention layers in one
stack, two leading dense layers, then experts under a sigmoid router with a
selection bias and no shared expert, a tied head.

Written from the model's published ``config.json`` (LiquidAI/LFM2-8B-A1B) and,
for what the config has no key for (marked +, each in the configuration's
``assumed``), the family's published modelling code. T tokens, hidden H:

    x       = E[tok]
  layer l:
    h       = RMSNorm(x; attn_norm)                                   (operator_norm)
    where layer_types[l] is ``conv``:
      B,C,u = split3(h @ conv_in)        conv_in [H, 3H], the thirds in that order
      z     = B * u
      c_t   = sum_{j=0..K-1} conv_w[j] * z_{t-K+1+j}     depthwise, causal, K = conv_L_cache
              taps, z before the sequence's start zero; no bias (conv_bias), NO activation
      o     = (C * c) @ conv_out
    where it is ``full_attention``:
      q,k,v = h @ wq [Hq, D], h @ wk [Hkv, D], h @ wv [Hkv, D]
      q, k  = RMSNorm over D (q_norm, k_norm) +
      q, k  = rotate-half rotary (rope_theta)
      o     = softmax(q k^T / sqrt(D) + causal) v @ wo                (GQA; no bias, window or gate)
    x       = x + o
    m       = RMSNorm(x; mlp_norm | moe_norm)                         (ffn_norm)
    f       = (silu(m @ wg) * (m @ wu)) @ wd        width intermediate_size, l < num_dense_layers
    f       = sum over the chosen e of w_e * expert_e(m)    else, each a SwiGLU of width
              moe_intermediate_size: s = sigmoid(m @ router) over num_experts; the chosen: the
              num_experts_per_tok largest of s + router_bias (use_expert_bias);  w = s[chosen]
              (no bias);  w = w / (sum(w) + 1e-6) (norm_topk_prob);  w = w * routed_scaling_factor
    x       = x + f

then a final RMSNorm and the tied head +. ``jax.numpy``, float32, matmul
precision "highest", one sequence, the whole context at once: no cache, no
state, no kernels, no pages. Imports nothing from the program under test.

The program keeps FOUR stacks in ``params["layers"]``, each under names of its
own: the convolution operators (``conv_*``, leading axis the number of conv
layers), the attention operators (``wq`` ... ``k_norm``, the number of attention
layers), the leading dense layers (``dense_*``, num_dense_layers) and the expert
layers (``attn_norm``, ``moe_norm``, ``router``, ``router_bias``, ``wg``, ``wu``,
``wd``: num_hidden_layers - num_dense_layers); a layer's operator norm lies with
its FFN's stack. ``layer(i)`` slices EVERY declared leaf at index i (past a
stack's end the index is clamped): for layer l this file asks once for its
FFN's stack (``layer(l)`` or ``layer(l - num_dense_layers)``) and once for its
operator's, at the layer's ORDINAL among its kind, and reads from each view the
leaves of that stack alone.

Six mechanisms are switches of ``sizes`` (``assumed_mechanisms`` in the
configuration's file, all as the model has them where absent), so that a
control can run this file with one of them changed and show that the
comparison deciding ``correct`` sees it (``hack/lfm2_correct_control.py``):
``conv_lagged_taps`` (False: only the newest tap, so a lost tail is no fault),
``conv_b_gate``, ``conv_c_gate``, ``selection_bias`` ("selection" | "none"),
``qk_norm``, and ``conv_restart_every`` (n > 0: the convolution starts from
zero at every n-th position, what a program that dropped its carried tails at
chunk boundaries would compute).

Departures, for memory alone: the logits' rows are computed as they are read
(``moe_dsa_gqa._Logits``); ``forward`` waits for each layer before it asks for
the next one's weights, so the device never holds two layers' float32 views. An
expert a token did not choose gets the weight 0 for it, so running every expert
over every token and summing is the chosen experts' weighted sum.
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

CONV_LEAVES = ("conv_in", "conv_w", "conv_out")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
DENSE_LEAVES = ("dense_attn_norm", "dense_mlp_norm", "dense_wg", "dense_wu", "dense_wd")
EXPERT_LEAVES = ("attn_norm", "moe_norm", "router", "router_bias", "wg", "wu", "wd")
LEAVES = CONV_LEAVES + ATTENTION_LEAVES + DENSE_LEAVES + EXPERT_LEAVES
OPTIONAL_LEAVES = ()
MECHANISMS = {"conv_lagged_taps": True, "conv_b_gate": True, "conv_c_gate": True,
              "selection_bias": "selection", "qk_norm": True, "conv_restart_every": 0}


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "vocab_size",
        "rope_theta", "intermediate_size", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
        "num_dense_layers", "routed_scaling_factor", "conv_L_cache")}
    out["rms_norm_eps"] = model["norm_eps"]
    out["head_dim"] = model.get("head_dim") or out["hidden_size"] // out["num_attention_heads"]
    out["layer_types"] = tuple(model["layer_types"])
    if len(out["layer_types"]) != out["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(out['layer_types'])} entries for {out['num_hidden_layers']} layers")
    if set(out["layer_types"]) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {sorted(set(out['layer_types']))}: conv or full_attention")
    if model.get("conv_bias") or not model.get("norm_topk_prob") or not model.get("use_expert_bias"):
        raise ValueError("this reference is the convolution with no bias under the sigmoid router with a "
                         "selection bias and normalised weights")
    chosen = {**MECHANISMS, **(model.get("assumed_mechanisms") or {})}
    if set(chosen) - set(MECHANISMS) or chosen["selection_bias"] not in ("selection", "none"):
        raise ValueError(f"assumed_mechanisms {chosen}: the switches are {MECHANISMS}")
    out.update(chosen)
    return out


def short_conv(h, w, sizes):
    """The gated short convolution on h [T, H], one sequence from its start."""
    T, K = h.shape[0], sizes["conv_L_cache"]
    b, c, u = jnp.split(h @ w["conv_in"], 3, axis=-1)
    z = b * u if sizes["conv_b_gate"] else u
    y = w["conv_w"][K - 1] * z
    t = jnp.arange(T)
    every = sizes["conv_restart_every"]
    for lag in range(1, K if sizes["conv_lagged_taps"] else 1):
        back = jnp.concatenate([jnp.zeros((lag, z.shape[1]), z.dtype), z[:T - lag]])
        if every:   # the input ``lag`` back lies before the last restart: forgotten
            back = jnp.where((t % every >= lag)[:, None], back, 0.0)
        y = y + w["conv_w"][K - 1 - lag] * back
    return ((c * y) if sizes["conv_c_gate"] else y) @ w["conv_out"]


def attention(h, w, positions, sizes):
    Hq, Hkv, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    T, eps = h.shape[0], sizes["rms_norm_eps"]
    q, k = (h @ w["wq"]).reshape(T, Hq, D), (h @ w["wk"]).reshape(T, Hkv, D)
    if sizes["qk_norm"]:
        q, k = dense.rms_norm(q, w["q_norm"], eps), dense.rms_norm(k, w["k_norm"], eps)
    q, k = dense.rotary(q, positions, sizes["rope_theta"]), dense.rotary(k, positions, sizes["rope_theta"])
    return dense.attention(q, k, (h @ w["wv"]).reshape(T, Hkv, D), None).reshape(T, Hq * D) @ w["wo"]


def route(m, w, sizes):
    """Routing weights [T, k] and the chosen experts [T, k] for tokens m [T, H]."""
    scores = jax.nn.sigmoid(m @ w["router"])
    select = scores if sizes["selection_bias"] == "none" else scores + w["router_bias"]
    _, idx = jax.lax.top_k(select, sizes["num_experts_per_tok"])
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    return gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-6) * sizes["routed_scaling_factor"], idx


def block(x, ffn, op, positions, sizes, is_conv, is_dense):
    """One decoder layer. ``ffn``: the layer's norms and its FFN's leaves under
    the expert layers' names (a dense layer's with ``dense_`` taken off);
    ``op``: its operator's. Float32 matrices [in, out] and vectors."""
    eps = sizes["rms_norm_eps"]
    h = dense.rms_norm(x, ffn["attn_norm"], eps)
    x = x + (short_conv(h, op, sizes) if is_conv else attention(h, op, positions, sizes))
    if is_dense:
        m = dense.rms_norm(x, ffn["mlp_norm"], eps)
        return x + (jax.nn.silu(m @ ffn["wg"]) * (m @ ffn["wu"])) @ ffn["wd"]
    m = dense.rms_norm(x, ffn["moe_norm"], eps)
    return x + sparse.experts(m, *route(m, ffn, sizes), ffn)


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``,
    the rows computed as they are read."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(token_ids))
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        step = jax.jit(lambda x, ffn, op, is_conv, is_dense: block(x, ffn, op, positions, sizes, is_conv, is_dense),
                       static_argnums=(3, 4))
        lead, ordinal = sizes["num_dense_layers"], {"conv": 0, "full_attention": 0}
        for i, kind in enumerate(sizes["layer_types"]):
            # The operator's view first: what is kept of it is small, so the
            # device holds one whole float32 view at a time.
            mine = CONV_LEAVES if kind == "conv" else ATTENTION_LEAVES
            op = {n: a for n, a in layer(ordinal[kind]).items() if n in mine}
            ordinal[kind] += 1
            if i < lead:
                ffn = {n[len("dense_"):]: a for n, a in layer(i).items() if n in DENSE_LEAVES}
            else:
                ffn = {n: a for n, a in layer(i - lead).items() if n in EXPERT_LEAVES}
            x = jax.block_until_ready(step(x, ffn, op, kind == "conv", i < lead))
            del ffn, op
        return sparse._Logits(dense.rms_norm(x, final_norm, sizes["rms_norm_eps"]), head)
