"""Plain reference of the Granite-4.0-H decoder (``model_type``
``granitemoehybrid``): Mamba-2 mixers beside a few grouped-query attention
layers with no position encoding in one stack, four multipliers, and in every
layer experts under a softmax router beside a shared gated MLP; a tied head.

Written from the model's published ``config.json`` (ibm-granite/
granite-4.0-h-small) and, for what the config has no key for (marked +, each in
the configuration's ``assumed``), the family's published modelling code. T
tokens, hidden H, e = embedding_multiplier, r = residual_multiplier:

    x       = e * E[tok]
  layer l:
    h       = RMSNorm(x; attn_norm)
    where layer_types[l] is ``mamba`` (Di = mamba_n_heads x mamba_d_head, N = mamba_d_state, one group):
      z,u,dt  = split(h @ in_proj)          in_proj [H, Di + (Di + 2 N) + heads], in that order +, no bias
      c_t     = silu(conv_b + sum_{j=0..K-1} conv_w[j] * u_{t-K+1+j})     depthwise, causal, K = mamba_d_conv,
                u before the sequence's start zero
      xs,B,C  = split(c)                    Di, N, N +
      Delta_t = softplus(dt_t + dt_bias) +;   A = -exp(A_log)               a scalar a head
      S_t     = exp(Delta_t A) S_{t-1} + Delta_t xs_t B_t^T                 a head: S [d_head, N], S_{-1} = 0
      y_t     = S_t C_t + D xs_t
      o       = (ssm_norm * rmsnorm_over_Di(y * silu(z))) @ out_proj        the gate BEFORE the norm +, eps rms_norm_eps
    where it is ``attention``:
      q,k,v   = h @ wq [Hq, D], h @ wk [Hkv, D], h @ wv [Hkv, D]            no bias, NO position encoding (nope)
      o       = softmax(attention_multiplier * q k^T + causal) v @ wo
    x       = x + r * o
    m       = RMSNorm(x; moe_norm)
    f       = sum over the chosen e of w_e * expert_e(m) + shared(m)
              chosen: the num_experts_per_tok largest of m @ router;  w = softmax over THOSE logits +;
              expert_e(m) = (silu(m @ wg[e]) * (m @ wu[e])) @ wd[e], width intermediate_size (the published
              fused input projection's halves +);  shared: the same form at shared_intermediate_size, added once
    x       = x + r * f

then a final RMSNorm and the tied head, its logits divided by logits_scaling.
The recurrence runs token by token (``lax.scan``): no blocks, no cache, no
kernels, no pages. ``jax.numpy``, float32, matmul precision "highest", one
sequence from its start. Imports nothing from the program under test.

The program keeps THREE stacks in ``params["layers"]``, each under names of its
own: the Mamba-2 mixers (``in_proj`` ... ``out_proj``, leading axis the number of
mamba layers), the attention operators (``wq`` ... ``wo``, the number of attention
layers) and every layer's norms, router, experts and shared MLP
(num_hidden_layers). ``layer(i)`` slices EVERY declared leaf at index i (past a
stack's end the index is clamped): for layer l this file asks for the layer's
own leaves (``layer(l)``: once for its first norm, once for its FFN's) and for
its operator's, at the layer's ORDINAL among its kind, and reads from each view
the leaves of that stack alone.

Four mechanisms are switches of ``sizes`` (``assumed_mechanisms`` in the
configuration's file, all as the model has them where absent), so that a
control can run this file with one of them changed and show that the
comparison deciding ``correct`` sees it (``hack/granite_correct_control.py``):
``norm_gate`` (False: the norm reads y alone), ``shared_mlp`` (False: the routed
sum alone), ``state_restart_from_end`` (k > 0: every mixer's state is zero in
front of the k-th token from the sequence's end: what a program that lost a
slot's state between the step that ends its prompt and its first decode step
would compute, with k the tokens fed back) and ``state_restart_every`` (n > 0:
zero in front of every n-th position: a state lost at every chunk boundary of n).
The multipliers are keys of the configuration: a control changes the key.

Departures, for memory alone: attention runs a block of queries at a time
(a whole 9,000-token probe's scores are 10 GB); the logits' rows are computed
as they are read (``moe_dsa_gqa._Logits``); ``forward`` runs a layer's mixer and
its FFN as two programs and waits for each before it asks for the next view of
the weights, so the device never holds two float32 views, nor the experts
beside the mixer's temporaries. An expert a token did not choose gets the
weight 0 for it, so running every expert over every token and summing is the
chosen experts' weighted sum.
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

MAMBA_LEAVES = ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm", "out_proj")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
LAYER_LEAVES = ("attn_norm", "moe_norm", "router", "wg", "wu", "wd", "shared_wg", "shared_wu", "shared_wd")
LEAVES = MAMBA_LEAVES + ATTENTION_LEAVES + LAYER_LEAVES
OPTIONAL_LEAVES = ()
MECHANISMS = {"norm_gate": True, "shared_mlp": True, "state_restart_from_end": 0, "state_restart_every": 0}
QUERY_BLOCK = 256


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "vocab_size",
        "intermediate_size", "shared_intermediate_size", "num_local_experts", "num_experts_per_tok", "rms_norm_eps",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
        "embedding_multiplier", "attention_multiplier", "residual_multiplier", "logits_scaling")}
    out["head_dim"] = model.get("head_dim") or out["hidden_size"] // out["num_attention_heads"]
    out["layer_types"] = tuple(model["layer_types"])
    if len(out["layer_types"]) != out["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(out['layer_types'])} entries for {out['num_hidden_layers']} layers")
    if set(out["layer_types"]) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {sorted(set(out['layer_types']))}: mamba or attention")
    if (out["mamba_n_groups"] != 1 or model.get("position_embedding_type") != "nope" or model.get("attention_bias")
            or model.get("mamba_proj_bias") or not model.get("mamba_conv_bias")
            or out["mamba_n_heads"] * out["mamba_d_head"] != model["mamba_expand"] * out["hidden_size"]):
        raise ValueError("this reference is the Mamba-2 mixer of one group with a biased convolution and no "
                         "projection bias, beside attention with no bias and no position encoding")
    chosen = {**MECHANISMS, **(model.get("assumed_mechanisms") or {})}
    if set(chosen) - set(MECHANISMS):
        raise ValueError(f"assumed_mechanisms {chosen}: the switches are {MECHANISMS}")
    out.update(chosen)
    return out


def mamba2(h, w, sizes):
    """The Mamba-2 mixer on h [T, H], one sequence from its start."""
    T = h.shape[0]
    Hh, P, N, K = sizes["mamba_n_heads"], sizes["mamba_d_head"], sizes["mamba_d_state"], sizes["mamba_d_conv"]
    Di = Hh * P
    proj = h @ w["in_proj"]
    z, u, dt = proj[:, :Di], proj[:, Di:2 * Di + 2 * N], proj[:, 2 * Di + 2 * N:]
    c = w["conv_b"] + w["conv_w"][K - 1] * u
    for lag in range(1, K):
        c = c + w["conv_w"][K - 1 - lag] * jnp.concatenate([jnp.zeros((lag, u.shape[1]), u.dtype), u[:T - lag]])
    c = jax.nn.silu(c)
    xs, B, C = c[:, :Di].reshape(T, Hh, P), c[:, Di:Di + N], c[:, Di + N:]
    delta = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    t = jnp.arange(T)
    restart = jnp.zeros((T,), bool)
    if sizes["state_restart_every"]:
        restart = restart | (t % sizes["state_restart_every"] == 0)
    if sizes["state_restart_from_end"]:
        restart = restart | (t == T - sizes["state_restart_from_end"])

    def step(S, tok):
        x_t, d_t, b_t, c_t, zero = tok
        S = jnp.where(zero, 0.0, S)
        S = jnp.exp(d_t * A)[:, None, None] * S + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return S, jnp.sum(S * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((Hh, P, N), jnp.float32), (xs, delta, B, C, restart))
    y = (y + w["D"][None, :, None] * xs).reshape(T, Di)
    if sizes["norm_gate"]:
        y = y * jax.nn.silu(z)
    return dense.rms_norm(y, w["ssm_norm"], sizes["rms_norm_eps"]) @ w["out_proj"]


def attention(h, w, sizes):
    """Causal grouped-query attention with no position encoding, scores times
    attention_multiplier, a block of queries at a time."""
    Hq, Hkv, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    T = h.shape[0]
    q = (h @ w["wq"]).reshape(T, Hq, D)
    k = jnp.repeat((h @ w["wk"]).reshape(T, Hkv, D), Hq // Hkv, axis=1)
    v = jnp.repeat((h @ w["wv"]).reshape(T, Hkv, D), Hq // Hkv, axis=1)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, T)
        scores = jnp.einsum("thd,shd->hts", q[start:end], k[:end]) * sizes["attention_multiplier"]
        mask = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs, v[:end]))
    return jnp.concatenate(out).reshape(T, Hq * D) @ w["wo"]


def route(m, w, sizes):
    """Routing weights [T, k] and the chosen experts [T, k] for tokens m [T, H]."""
    top, idx = jax.lax.top_k(m @ w["router"], sizes["num_experts_per_tok"])
    return jax.nn.softmax(top, axis=-1), idx


def mixer_block(x, norm, op, sizes, is_mamba):
    """A layer's first half: x + r * Mixer(RMSNorm(x)). ``op``: the operator's
    leaves; ``norm``: the layer's attn_norm."""
    h = dense.rms_norm(x, norm, sizes["rms_norm_eps"])
    return x + sizes["residual_multiplier"] * (mamba2(h, op, sizes) if is_mamba else attention(h, op, sizes))


def ffn_block(x, own, sizes):
    """A layer's second half: x + r * (MoE(g) + Shared(g)), g = RMSNorm(x).
    ``own``: the layer's moe_norm, router, experts and shared MLP."""
    m = dense.rms_norm(x, own["moe_norm"], sizes["rms_norm_eps"])
    f = sparse.experts(m, *route(m, own, sizes), own)
    if sizes["shared_mlp"]:
        f = f + (jax.nn.silu(m @ own["shared_wg"]) * (m @ own["shared_wu"])) @ own["shared_wd"]
    return x + sizes["residual_multiplier"] * f


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``,
    the rows computed as they are read. A layer runs in two programs, each
    with its own float32 view of the weights and nothing of the other's: beside
    the int8 tree and the float32 tied head (1.6 GB) a chip has no room for a
    layer's experts (2.7 GB in float32) AND a 9,000-token mixer's temporaries
    (2 GB) at once (my chip run, PR 50: RESOURCE_EXHAUSTED by 0.85 GB)."""
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32) * sizes["embedding_multiplier"]
        mix = jax.jit(lambda x, norm, op, is_mamba: mixer_block(x, norm, op, sizes, is_mamba), static_argnums=(3,))
        ffn = jax.jit(lambda x, own: ffn_block(x, own, sizes))
        ordinal = {"mamba": 0, "attention": 0}
        for i, kind in enumerate(sizes["layer_types"]):
            norm = layer(i)["attn_norm"]
            mine = MAMBA_LEAVES if kind == "mamba" else ATTENTION_LEAVES
            op = {n: a for n, a in layer(ordinal[kind]).items() if n in mine}
            ordinal[kind] += 1
            x = jax.block_until_ready(mix(x, norm, op, kind == "mamba"))
            del op
            own = {n: a for n, a in layer(i).items() if n in LAYER_LEAVES}
            x = jax.block_until_ready(ffn(x, own))
            del own
        return sparse._Logits(dense.rms_norm(x, final_norm, sizes["rms_norm_eps"]), head / sizes["logits_scaling"])
