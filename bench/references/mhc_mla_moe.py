"""Plain reference of the Xing4.0 decoder block (``model_type`` ``xing4_0``):
multi-head latent attention under YaRN, ``hc_mult`` residual streams a token
mixed by manifold-constrained hyper-connections around every block, leading
dense layers before expert layers, a sigmoid router with a selection bias and
an always-on shared expert.

Written from the model's published ``config.json`` (XingChen-AGI/Xing4.0-29B-A4B)
and, for what the config has no key for (marked +, each in the configuration's
``assumed``), from the papers its keys name: arXiv:2512.24880 for ``hc_*`` and
``mhc_*``, the DeepSeek-V2/V3 reports and modelling code for the latent
attention, the router and YaRN. T tokens, hidden C, n = ``hc_mult`` streams:

    X       = E[tok] in each of the n rows +                           [T, n, C]
  layer l, for each of its two blocks F (attention, then MLP or experts), with
  the block's own phi [n C, 2n + n n], alpha [3], bias [2n + n n]:
    x~      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)             (no weight)
    p       = x~ phi
    H_pre   = sigmoid(alpha_0 p[:n] + bias[:n])                        [n]
    H_post  = 2 sigmoid(alpha_1 p[n:2n] + bias[n:2n])                  [n]
    M       = exp(clip(alpha_2 p[2n:] + bias[2n:], mhc_h_res_clamp_min, _max))   [n, n], row-major +
    hc_sinkhorn_iters times: M = M / (rowsum(M) + hc_eps); M = M / (colsum(M) + hc_eps) +
    H_res   = M
    u       = H_pre X                                                  [C]
    X       = H_res X + H_post^T F(RMSNorm(u; the block's input norm))
  attention F(a), heads Hq, no bias:
    c_q     = RMSNorm(a @ wdq; q_a_norm);  [q_nope (qk_nope_head_dim), q_rope (qk_rope_head_dim)] = c_q @ wuq, a head
    [c, r]  = a @ wdkv;  c = RMSNorm(c; kv_a_norm) (kv_lora_rank);  r: ONE rotary key (qk_rope_head_dim) for all heads
    q_rope, r = rotate-half rotary under YaRN's inverse frequencies (below)
    [k_nope (qk_nope_head_dim), v (v_head_dim)] = c @ wukv, a head
    s       = (q_nope . k_nope + q_rope . r) / sqrt(qk_nope_head_dim + qk_rope_head_dim) * m^2, causal softmax
    F       = concat_heads(softmax(s) v) @ wo
  YaRN (rope_scaling: factor f, original L, beta_fast, beta_slow, theta over the rotary dims D):
    inv_j   = theta^(-2j/D);  d(b) = D ln(L / (2 pi b)) / (2 ln theta);  lo = floor(d(beta_fast)), hi = ceil(d(beta_slow))
    ramp_j  = clip((j - lo) / (hi - lo), 0, 1);  inv_j = inv_j / f * ramp_j + inv_j (1 - ramp_j)
    m       = 0.1 mscale_all_dim ln f + 1;  cos, sin times mscale's ratio to mscale_all_dim (1 here)
  MLP, l < first_k_dense_replace:  (silu(h @ wg) * (h @ wu)) @ wd, width intermediate_size, h = RMSNorm(u; mlp_norm)
  experts, else, h = RMSNorm(u; moe_norm):
    s = sigmoid(h @ router) over n_routed_experts; the chosen: the num_experts_per_tok largest of
    s + router_bias (noaux_tc; n_group = topk_group = 1: no grouping); w = s[chosen] (no bias);
    w = w / (sum(w) + 1e-20) (norm_topk_prob); w = w * routed_scaling_factor;
    F = sum over the chosen of w_e expert_e(h) + shared(h), each a SwiGLU of moe_intermediate_size
    (the shared one n_shared_experts times that)

then x = sum of the n rows +, a final RMSNorm and an untied head. ``jax.numpy``,
float32, matmul precision "highest", one sequence, the whole context at once: no
cache, no pages, no absorption (k_nope and v are materialised for every head of
every token). Imports nothing from the program under test.

Departures from the published description: the next-token-prediction module
(``num_nextn_predict_layers``) does not enter the next-token logits and is not
computed (the configuration's ``reduced``); the lines marked + are assumed, each
with its source in the configuration's ``assumed``. For memory alone: attention
runs over blocks of ``QUERY_BLOCK`` queries (every query still sees every key
before it); the logits' rows are computed as they are read; ``forward`` waits for
each layer before it asks for the next one's weights.

The program keeps the two kinds of layer as two stacks in ``params["layers"]``
(``afmoe.py``'s docstring): this file asks ``layer(l)`` for dense layer l and
``layer(l - first_k_dense_replace)`` for an expert layer, and reads only the
stack it asked for.

Switches of ``sizes`` (``assumed_mechanisms`` in the configuration's file, all
True where absent), so that a control can run this file with one mechanism
changed and show that the comparison deciding ``correct`` sees it
(``hack/xing_correct_control.py``): ``residual_mixing`` False replaces the
hyper-connections by ONE stream, x + F(norm(x)); ``rope_scores`` False drops
q_rope . r from the scores; ``matrix_rounding`` "int4" re-rounds every matrix
(``MATRICES``) to 4 bits in groups of 128 along its input axis, symmetric,
round to nearest: the weights one precision below the int8 the configuration
states.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location("bench_reference_moe_dsa_gqa",
                                               Path(__file__).with_name("moe_dsa_gqa.py"))
sparse = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sparse)
dense = sparse.dense

HC = tuple(f"hc_{block}_{leaf}" for block in ("attn", "ffn") for leaf in ("phi", "alpha", "bias"))
ATTENTION = ("attn_norm", "wdq", "q_a_norm", "wuq", "wdkv", "kv_a_norm", "wukv", "wo") + HC
DENSE_LEAVES = tuple("dense_" + n for n in ATTENTION + ("mlp_norm", "wg", "wu", "wd"))
EXPERT_LEAVES = ATTENTION + ("moe_norm", "router", "router_bias", "wg", "wu", "wd",
                             "shared_wg", "shared_wu", "shared_wd")
LEAVES = EXPERT_LEAVES + DENSE_LEAVES
OPTIONAL_LEAVES = ()
QUERY_BLOCK = 256
MECHANISMS = {"residual_mixing": True, "rope_scores": True, "matrix_rounding": None}
MATRICES = ("wdq", "wuq", "wdkv", "wukv", "wo", "wg", "wu", "wd", "shared_wg", "shared_wu", "shared_wd")


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "vocab_size", "rope_theta", "rms_norm_eps",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts", "num_experts_per_tok",
        "first_k_dense_replace", "n_shared_experts", "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
        "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")}
    scaling = dict(model["rope_scaling"])
    if scaling.get("type", scaling.get("rope_type")) != "yarn" or scaling["mscale"] != scaling["mscale_all_dim"]:
        raise ValueError("this reference is YaRN with mscale equal to mscale_all_dim")
    out["rope_scaling"] = scaling
    if model.get("scoring_func") != "sigmoid" or not model.get("norm_topk_prob") or model.get("topk_method") != "noaux_tc":
        raise ValueError("this reference is the sigmoid router with a selection bias and normalised weights")
    if model.get("n_group", 1) != 1 or model.get("topk_group", 1) != 1:
        raise ValueError("this reference routes over one group")
    if model.get("attention_bias") or model.get("hidden_act", "silu") != "silu":
        raise ValueError("this reference has no attention bias and SwiGLU blocks")
    chosen = {**MECHANISMS, **(model.get("assumed_mechanisms") or {})}
    if set(chosen) - set(MECHANISMS) or chosen["matrix_rounding"] not in (None, "int4"):
        raise ValueError(f"assumed_mechanisms {chosen}: the switches are {MECHANISMS}, matrix_rounding None or int4")
    out.update(chosen)
    return out


def yarn_inv_freq(dim: int, theta: float, scaling: dict):
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    old = scaling["original_max_position_embeddings"]
    at = lambda turns: dim * math.log(old / (turns * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = max(math.floor(at(scaling["beta_fast"])), 0), min(math.ceil(at(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    return inv / scaling["factor"] * ramp + inv * (1 - ramp)


def yarn_m(scaling: dict) -> float:
    return 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0 if scaling["factor"] > 1 else 1.0


def int4_rounded(w, group: int = 128):
    """w [..., in, out] re-rounded to 4 bits in groups along its input axis."""
    lead, cin, out = w.shape[:-2], w.shape[-2], w.shape[-1]
    g = w.reshape(*lead, cin // min(group, cin), min(group, cin), out)
    scale = jnp.maximum(jnp.max(jnp.abs(g), axis=-2, keepdims=True), 1e-12) / 7.0
    return (jnp.clip(jnp.round(g / scale), -8, 7) * scale).reshape(w.shape)


_int4_in_place = jax.jit(int4_rounded, donate_argnums=(0,))


def rotary(x, positions, inv_freq):
    """Rotate-half rotary of x [T, heads, D] at the given inverse frequencies."""
    half = x.shape[-1] // 2
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def attention(q, k, v, scale):
    """Causal softmax attention a block of ``QUERY_BLOCK`` queries at a time:
    q, k [T, H, Dk], v [T, H, Dv], one key head a query head."""
    T, H, _ = q.shape
    n = -(-T // QUERY_BLOCK)
    key_pos = jnp.arange(T)

    def one(args):
        qb, pos = args
        scores = jnp.einsum("qhd,shd->hqs", qb, k) * scale
        probs = jax.nn.softmax(jnp.where((key_pos[None, :] <= pos[:, None])[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v)

    pos = jnp.minimum(jnp.arange(n * QUERY_BLOCK), T - 1).reshape(n, QUERY_BLOCK)
    qb = jnp.pad(q, ((0, n * QUERY_BLOCK - T), (0, 0), (0, 0))).reshape(n, QUERY_BLOCK, H, q.shape[-1])
    return jax.lax.map(one, (qb, pos)).reshape(n * QUERY_BLOCK, H, v.shape[-1])[:T]


def latent_attention(a, w, positions, sizes):
    """F(a) of the attention block: a [T, C] the block's normed input."""
    T, eps = a.shape[0], sizes["rms_norm_eps"]
    Hq, Dn, Dr, Dv = (sizes[k] for k in ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rank, inv_freq = sizes["kv_lora_rank"], yarn_inv_freq(sizes["qk_rope_head_dim"], sizes["rope_theta"], sizes["rope_scaling"])
    q = (dense.rms_norm(a @ w["wdq"], w["q_a_norm"], eps) @ w["wuq"]).reshape(T, Hq, Dn + Dr)
    down = a @ w["wdkv"]
    c = dense.rms_norm(down[:, :rank], w["kv_a_norm"], eps)
    r = rotary(down[:, None, rank:], positions, inv_freq)                       # [T, 1, Dr]
    up = (c @ w["wukv"]).reshape(T, Hq, Dn + Dv)
    q_rope = rotary(q[..., Dn:], positions, inv_freq)
    if sizes["rope_scores"]:
        qq = jnp.concatenate([q[..., :Dn], q_rope], axis=-1)
        kk = jnp.concatenate([up[..., :Dn], jnp.broadcast_to(r, (T, Hq, Dr))], axis=-1)
    else:
        qq, kk = q[..., :Dn], up[..., :Dn]
    scale = (Dn + Dr) ** -0.5 * yarn_m(sizes["rope_scaling"]) ** 2
    return attention(qq, kk, up[..., Dn:], scale).reshape(T, Hq * Dv) @ w["wo"]


def route(h, w, sizes):
    scores = jax.nn.sigmoid(h @ w["router"])
    _, idx = jax.lax.top_k(scores + w["router_bias"], sizes["num_experts_per_tok"])
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    return gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"], idx


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def hyper_maps(X, phi, alpha, bias, sizes):
    """H_pre [T, n], H_post [T, n], H_res [T, n, n] of the streams X [T, n, C]."""
    T, n, C = X.shape
    flat = X.reshape(T, n * C)
    p = (flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + sizes["rms_norm_eps"])) @ phi
    pre = jax.nn.sigmoid(alpha[0] * p[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * p[:, n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * p[:, 2 * n:] + bias[2 * n:], sizes["mhc_h_res_clamp_min"],
                         sizes["mhc_h_res_clamp_max"])).reshape(T, n, n)
    for _ in range(sizes["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + sizes["hc_eps"])
        m = m / (jnp.sum(m, axis=1, keepdims=True) + sizes["hc_eps"])
    return pre, post, m


def connected(X, w, block, sizes, fn):
    """One block around the streams: ``fn(u)`` is F with its input norm."""
    if not sizes["residual_mixing"]:
        return X + fn(X[:, 0])[:, None, :]            # one stream, carried as [T, 1, C]
    pre, post, res = hyper_maps(X, w[f"hc_{block}_phi"], w[f"hc_{block}_alpha"], w[f"hc_{block}_bias"], sizes)
    y = fn(jnp.einsum("tn,tnc->tc", pre, X))
    return jnp.einsum("tij,tjc->tic", res, X) + post[:, :, None] * y[:, None, :]


def attention_block(X, w, positions, sizes):
    """A layer's first block around the streams."""
    return connected(X, w, "attn", sizes, lambda u: latent_attention(
        dense.rms_norm(u, w["attn_norm"], sizes["rms_norm_eps"]), w, positions, sizes))


def ffn_block(X, w, sizes, is_dense):
    """A layer's second block around the streams: the MLP or the experts."""
    eps = sizes["rms_norm_eps"]

    def ffn(u):
        if is_dense:
            return swiglu(dense.rms_norm(u, w["mlp_norm"], eps), w["wg"], w["wu"], w["wd"])
        h = dense.rms_norm(u, w["moe_norm"], eps)
        f = sparse.experts(h, *route(h, w, sizes), w)
        return f + swiglu(h, w["shared_wg"], w["shared_wu"], w["shared_wd"]) if sizes["n_shared_experts"] else f

    return connected(X, w, "ffn", sizes, ffn)


def block(X, w, positions, sizes, is_dense):
    """One decoder layer. ``w``: float32 matrices [in, out] and vectors, under
    the expert layers' names (a dense layer's with ``dense_`` taken off)."""
    return ffn_block(attention_block(X, w, positions, sizes), w, sizes, is_dense)


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``,
    the rows computed as they are read."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(token_ids))
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        streams = sizes["hc_mult"] if sizes["residual_mixing"] else 1
        X = jnp.broadcast_to(x[:, None, :], (x.shape[0], streams, x.shape[1]))
        step = jax.jit(lambda X, w, is_dense: block(X, w, positions, sizes, is_dense), static_argnums=(2,))
        lead = sizes["first_k_dense_replace"]
        for i in range(sizes["num_hidden_layers"]):
            if i < lead:
                w = {n[len("dense_"):]: a for n, a in layer(i).items() if n in DENSE_LEAVES}
            else:
                w = {n: a for n, a in layer(i - lead).items() if n in EXPERT_LEAVES}
            if sizes["matrix_rounding"] == "int4":   # in place, a leaf at a time: a layer's view is 3 GB
                for n in [n for n in w if n in MATRICES]:
                    w[n] = _int4_in_place(w.pop(n))
            X = jax.block_until_ready(step(X, w, i < lead))
            del w
        return sparse._Logits(dense.rms_norm(jnp.sum(X, axis=1), final_norm, sizes["rms_norm_eps"]), head)
