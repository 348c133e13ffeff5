"""Plain reference of the Jamba decoder (``model_type`` ``jamba``; the block of
arXiv:2403.19887, the mixer of arXiv:2312.00752) as AI21-Jamba2-3B configures it:
Mamba-1 mixers whose Delta, B and C are RMS-normed, beside a few grouped-query
attention layers with NO position encoding, a dense gated MLP in every layer
(``num_experts`` 1), a tied head.

Written from the model's published ``config.json`` (ai21labs/AI21-Jamba2-3B) and,
for what the config has no key for (marked +, each in the configuration's
``assumed``), the family's published modelling code. T tokens, hidden H:

    x       = E[tok]
  layer l:
    h       = RMSNorm(x; attn_norm)                                   (the family's input_layernorm)
    where (l - attn_layer_offset) % attn_layer_period == 0 +: attention
      q,k,v   = h @ wq [Hq, D], h @ wk [Hkv, D], h @ wv [Hkv, D]      no bias, NO position encoding
      o       = softmax(q k^T / sqrt(D) + causal) v @ wo
    else: the Mamba mixer (Di = mamba_expand x H, N = mamba_d_state, R = mamba_dt_rank, K = mamba_d_conv)
      u, z    = split(h @ in_proj)            in_proj [H, 2 Di], in that order +, no bias (mamba_proj_bias false)
      c_t     = silu(conv_b + sum_{j=0..K-1} conv_w[j] * u_{t-K+1+j})   depthwise, causal, u before the start zero
      dt,B,C  = split(c @ x_proj)             R, N, N +, no bias
      dt,B,C  = RMSNorm(dt; dt_norm), RMSNorm(B; b_norm), RMSNorm(C; c_norm)   + the family's dt / b / c_layernorm,
                eps rms_norm_eps
      Delta_t = softplus(dt_t @ dt_proj + dt_bias);   A = -exp(A_log)   [Di, N]
      S_t     = exp(Delta_t A) * S_{t-1} + (Delta_t c_t) B_t^T          S [Di, N], S_{-1} = 0
      y_t     = S_t C_t + D * c_t
      o       = (y * silu(z)) @ out_proj
    x       = x + o
    m       = RMSNorm(x; mlp_norm)                                     (the family's pre_ff_layernorm)
    x       = x + (silu(m @ wg) * (m @ wu)) @ wd

then a final RMSNorm and the tied head. The recurrence runs token by token
(``lax.scan``): no chunks, no cache, no kernels, no pages, no batching.
``jax.numpy``, float32, matmul precision "highest", one sequence from its start.
Imports nothing from the program under test.

The program keeps THREE stacks in ``params["layers"]``, each under names of its
own: the mixers (``in_proj`` ... ``out_proj``, leading axis the number of Mamba
layers), the attention operators (``wq`` ... ``wo``, the attention layers) and
every layer's two norms and MLP (num_hidden_layers). ``layer(i)`` slices EVERY
declared leaf at index i (past a stack's end the index is clamped): for layer l
this file asks for the layer's own leaves (``layer(l)``) and for its operator's
at the layer's ORDINAL among its kind, and reads from each view the leaves of
that stack alone.

Four mechanisms are switches of ``sizes`` (``assumed_mechanisms`` in the
configuration's file, all as the model has them where absent), so that a control
can run this file with one of them changed and show that the comparison deciding
``correct`` sees it (``hack/jamba_correct_control.py``): ``inner_norms`` (False:
dt, B and C as W_x gives them), ``state_dtype`` ("bfloat16": the state rounded
after every token, what a store kept in bfloat16 holds), ``state_from_previous``
(True: every mixer starts from the state the SAME sequence left at its end, what
a slot serves a twin request from if position 0 does not zero it) and
``state_restart_every`` (n > 0: zero in front of every n-th position: a state
lost at every chunk boundary of n).

Departures, for memory alone: attention runs a block of queries at a time (a
whole 9,000-token probe's scores are 6.5 GB at 20 heads); the logits' rows are
computed as they are read (``moe_dsa_gqa._Logits``: 9,025 x 65,536 float32 is
2.4 GB); ``forward`` runs a layer's mixer and its MLP as two programs and waits
for each before it asks for the next view of the weights.
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

MAMBA_LEAVES = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias", "A_log", "D",
                "dt_norm", "b_norm", "c_norm", "out_proj")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
LAYER_LEAVES = ("attn_norm", "mlp_norm", "wg", "wu", "wd")
LEAVES = MAMBA_LEAVES + ATTENTION_LEAVES + LAYER_LEAVES
OPTIONAL_LEAVES = ()
MECHANISMS = {"inner_norms": True, "state_dtype": "float32", "state_from_previous": False, "state_restart_every": 0}
QUERY_BLOCK = 256


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "vocab_size",
        "intermediate_size", "rms_norm_eps", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
        "attn_layer_offset", "attn_layer_period")}
    out["head_dim"] = model.get("head_dim") or out["hidden_size"] // out["num_attention_heads"]
    # + the order of the layer types: attention where (l - offset) % period == 0, the family's rule.
    out["layer_types"] = tuple("attention" if (l - out["attn_layer_offset"]) % out["attn_layer_period"] == 0 else "mamba"
                               for l in range(out["num_hidden_layers"]))
    if (model.get("num_experts", 1) != 1 or model.get("mamba_proj_bias") or not model.get("mamba_conv_bias", True)
            or model.get("sliding_window") or model.get("hidden_act", "silu") != "silu"
            or not model.get("tie_word_embeddings")):
        raise ValueError("this reference is the dense Jamba block: one expert (a gated silu MLP in every layer), a "
                         "biased convolution, no projection bias, no window, a tied head")
    chosen = {**MECHANISMS, **(model.get("assumed_mechanisms") or {})}
    if set(chosen) - set(MECHANISMS):
        raise ValueError(f"assumed_mechanisms {chosen}: the switches are {MECHANISMS}")
    out.update(chosen)
    return out


def mamba(h, w, sizes):
    """The Mamba-1 mixer on h [T, H], one sequence from its start."""
    T = h.shape[0]
    N, K, R = sizes["mamba_d_state"], sizes["mamba_d_conv"], sizes["mamba_dt_rank"]
    Di = sizes["mamba_expand"] * sizes["hidden_size"]
    proj = h @ w["in_proj"]
    u, z = proj[:, :Di], proj[:, Di:]
    c = w["conv_b"] + w["conv_w"][K - 1] * u
    for lag in range(1, K):
        c = c + w["conv_w"][K - 1 - lag] * jnp.concatenate([jnp.zeros((lag, Di), u.dtype), u[:T - lag]])
    c = jax.nn.silu(c)
    low = c @ w["x_proj"]
    dt, B, C = low[:, :R], low[:, R:R + N], low[:, R + N:]
    if sizes["inner_norms"]:
        eps = sizes["rms_norm_eps"]
        dt, B, C = dense.rms_norm(dt, w["dt_norm"], eps), dense.rms_norm(B, w["b_norm"], eps), dense.rms_norm(C, w["c_norm"], eps)
    delta = jax.nn.softplus(dt @ w["dt_proj"] + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    restart = jnp.zeros((T,), bool)
    if sizes["state_restart_every"]:
        restart = jnp.arange(T) % sizes["state_restart_every"] == 0
    # (``reduce_precision`` and not a pair of casts: the chip's compiler drops a float32 -> bfloat16 -> float32 pair)
    kept = ((lambda S: jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7))
            if sizes["state_dtype"] == "bfloat16" else (lambda S: S))

    def step(S, tok):
        c_t, d_t, b_t, cc_t, zero = tok
        S = jnp.where(zero, 0.0, S)
        S = kept(jnp.exp(d_t[:, None] * A) * S + (d_t * c_t)[:, None] * b_t[None, :])
        return S, jnp.sum(S * cc_t[None, :], axis=-1)

    start = jnp.zeros((Di, N), jnp.float32)
    if sizes["state_from_previous"]:
        start, _ = jax.lax.scan(step, start, (c, delta, B, C, restart))
    _, y = jax.lax.scan(step, start, (c, delta, B, C, restart))
    return ((y + w["D"] * c) * jax.nn.silu(z)) @ w["out_proj"]


def attention(h, w, sizes):
    """Causal grouped-query attention with no position encoding, a block of queries at a time."""
    Hq, Hkv, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    T = h.shape[0]
    q = (h @ w["wq"]).reshape(T, Hq, D)
    k = jnp.repeat((h @ w["wk"]).reshape(T, Hkv, D), Hq // Hkv, axis=1)
    v = jnp.repeat((h @ w["wv"]).reshape(T, Hkv, D), Hq // Hkv, axis=1)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, T)
        scores = jnp.einsum("thd,shd->hts", q[start:end], k[:end]) * D ** -0.5
        mask = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs, v[:end]))
    return jnp.concatenate(out).reshape(T, Hq * D) @ w["wo"]


def mixer_block(x, norm, op, sizes, is_mamba):
    """A layer's first half: x + Mixer(RMSNorm(x))."""
    h = dense.rms_norm(x, norm, sizes["rms_norm_eps"])
    return x + (mamba(h, op, sizes) if is_mamba else attention(h, op, sizes))


def mlp_block(x, own, sizes):
    """A layer's second half: x + MLP(RMSNorm(x))."""
    m = dense.rms_norm(x, own["mlp_norm"], sizes["rms_norm_eps"])
    return x + (jax.nn.silu(m @ own["wg"]) * (m @ own["wu"])) @ own["wd"]


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``,
    the rows computed as they are read."""
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        mix = jax.jit(lambda x, norm, op, is_mamba: mixer_block(x, norm, op, sizes, is_mamba), static_argnums=(3,))
        mlp = jax.jit(lambda x, own: mlp_block(x, own, sizes))
        ordinal = {"mamba": 0, "attention": 0}
        for i, kind in enumerate(sizes["layer_types"]):
            norm = layer(i)["attn_norm"]
            mine = MAMBA_LEAVES if kind == "mamba" else ATTENTION_LEAVES
            op = {n: a for n, a in layer(ordinal[kind]).items() if n in mine}
            ordinal[kind] += 1
            x = jax.block_until_ready(mix(x, norm, op, kind == "mamba"))
            del op
            own = {n: a for n, a in layer(i).items() if n in LAYER_LEAVES}
            x = jax.block_until_ready(mlp(x, own))
            del own
        return sparse._Logits(dense.rms_norm(x, final_norm, sizes["rms_norm_eps"]), head)
