"""Plain reference of the Phi-4-mini-flash decoder (SambaY, arXiv:2507.06607):
a self-decoder of Mamba mixers beside windowed differential attention, one
full differential attention layer whose keys and values are the model's only
full-context cache, and a cross-decoder of gated memory units beside cross
attention that reads that one cache.

Written from the model's published ``config.json`` (microsoft/
Phi-4-mini-flash-reasoning) and the family's description; what the config has
no key for is the family's convention and is listed in the configuration's
``assumed``. With N layers, P = N / 2 pairs (an even layer, then an odd one)
and the middle pair M = P / 2, layer l of pair k = l // 2 is

    l even, k <= M   Mamba mixer (arXiv:2312.00752); pair M's also emits the memory m
    l odd,  k <  M   differential attention (arXiv:2410.05258) under the window
    l odd,  k == M   full differential attention: the shared cache
    l even, k >  M   gated memory unit on m
    l odd,  k >  M   cross differential attention: own query, pair M's keys and values

Every block: x = x + Mixer(LN(x; g1, b1)); x = x + W_fc2 (u * silu(g)) with
[g, u] = split(W_fc1 LN(x; g2, b2)), gate first. After the last layer
logits = LN(x; g_f, b_f) E^T, E the embedding. No position encoding anywhere.

    Mamba     [u, z] = split(W_in h);  c_t = silu(b + sum_j w[j] * u_{t-3+j}), u = 0 before the sequence
              [d, B, C] = split(W_x c);  Delta = softplus(W_dt d + b_dt);  A = -exp(A_log)
              S_t = exp(Delta_t A) * S_{t-1} + (Delta_t c_t) B_t^T, S_0 = 0;  y_t = S_t C_t + D * c_t
              out = W_out (y * silu(z));  m = y (before the gate)
    GMU       out = W_out^g (m * silu(W_in^g h))
    attention q = W_q h + b_q (Hq heads of D), k, v = split(W_kv h + b_kv) (Hkv heads of D each)
              q1_i = q_{2i}, q2_i = q_{2i+1}; k1_j = k_{2j}, k2_j = k_{2j+1}; v_j = [v_{2j}; v_{2j+1}]; j = i // (Hq / Hkv)
              A^s_i = softmax(q^s_i (k^s_j)^T / sqrt(D) + mask); the window counts the query: keys p, t - W < p <= t
              lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + linit, linit = 0.8 - 0.6 exp(-0.3 l)
              o_i = RMSNorm((A^1_i - lambda A^2_i) v_j; gain, eps) * (1 - linit);  out = W_o concat_i(o_i) + b_o

``jax.numpy``, float32, matmul precision "highest", one sequence, the whole
context at once: no cache, no kernels, no batching; S by a plain ``lax.scan``.
Imports nothing from the program under test.

Weights are data: ``layer(i)`` hands over EVERY stacked leaf's slice i in
float32 (an index past a shorter stack clamps), matrices ``[in, out]``. The
program stacks by pair, so pair k's leaves are ``layer(k)``: ``norms`` [2, 4, H]
(even layer, odd layer; g1, b1, g2, b2), ``fc1`` [2, H, 2I], ``fc2`` [2, I, H],
``wq`` ``bq`` ``wo`` ``bo`` ``lam`` [4, D] (lq1, lk1, lq2, lk2) ``sub_norm``,
``mix_out`` (the Mamba mixer's W_out for k <= M, the GMU's for k > M), and for
k <= M the Mamba leaves and ``wkv`` ``bkv``; the GMU's ``g_in`` is stacked
from pair M + 1, so it is ``layer(k - M - 1)``. ``final_norm`` is [2, H]: gain, bias.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LEAVES = ("norms", "fc1", "fc2", "m_in", "m_conv_w", "m_conv_b", "m_x", "m_dt", "m_dt_b", "m_A_log", "m_D",
          "mix_out", "g_in", "wq", "bq", "wkv", "bkv", "wo", "bo", "lam", "sub_norm")
OPTIONAL_LEAVES = ()
SUB_NORM_EPS = 1e-5


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "vocab_size", "sliding_window", "layer_norm_eps", "mamba_d_state", "mamba_d_conv", "mamba_expand",
        "mamba_dt_rank")}
    if out["num_hidden_layers"] % 4:
        raise ValueError("the layer pattern wants a multiple of four layers")
    if model.get("mb_per_layer", 2) != 2:
        raise ValueError("this reference is the one-Mamba-layer-in-two pattern (mb_per_layer 2)")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("this reference is the silu-gated MLP")
    return out


def layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def mlp(h, fc1, fc2):
    g, u = jnp.split(h @ fc1, 2, axis=-1)
    return (u * jax.nn.silu(g)) @ fc2


def mamba(h, w, s):
    """The mixer over one sequence h [T, H] -> (out [T, H], y [T, Di])."""
    T = h.shape[0]
    K, N, R = s["mamba_d_conv"], s["mamba_d_state"], s["mamba_dt_rank"]
    u, z = jnp.split(h @ w["m_in"], 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    c = w["m_conv_b"] + sum(w["m_conv_w"][j] * padded[j:j + T] for j in range(K))
    c = jax.nn.silu(c)
    d, B, C = jnp.split(c @ w["m_x"], [R, R + N], axis=-1)
    delta = jax.nn.softplus(d @ w["m_dt"] + w["m_dt_b"])
    A = -jnp.exp(w["m_A_log"])                                         # [Di, N]

    def step(S, t):
        dl, ct, Bt, Ct = t
        S = jnp.exp(dl[:, None] * A) * S + (dl * ct)[:, None] * Bt[None, :]
        return S, S @ Ct + w["m_D"] * ct

    _, y = jax.lax.scan(step, jnp.zeros_like(A), (delta, c, B, C))
    return (y * jax.nn.silu(z)) @ w["mix_out"], y


def diff_attention(q, k, v, w, l, window, s):
    """q [T, Hq, D] over keys and values [T, Hkv, D] of the same positions."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    q = q.reshape(T, Hq // 2, 2, D)
    k = jnp.repeat(k.reshape(T, Hkv // 2, 2, D), G, axis=1)             # pair i reads pair i // G
    v = jnp.repeat(v.reshape(T, Hkv // 2, 2 * D), G, axis=1)
    t, p = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = p <= t if window is None else (p <= t) & (p > t - window)
    scores = jnp.einsum("tisd,pisd->sitp", q, k) / math.sqrt(D)
    a = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
    linit = 0.8 - 0.6 * jnp.exp(-0.3 * l)
    lq1, lk1, lq2, lk2 = w["lam"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + linit
    o = jnp.einsum("itp,pie->tie", a[0] - lam * a[1], v)                # [T, Hq / 2, 2D]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + SUB_NORM_EPS) * w["sub_norm"]
    return (o * (1.0 - linit)).reshape(T, Hq * D) @ w["wo"] + w["bo"]


def pair(x, m, kv, w, g_in, k, where, s):
    """Layers 2k and 2k + 1 (``k`` may be traced; ``where`` says on which side
    of the middle pair M it lies: -1, 0 or 1). ``m`` the memory and ``kv`` the
    shared keys and values, None until the middle pair has made them. Returns
    x, m, kv."""
    l_odd = 2.0 * k + 1.0
    k, M = where, 0     # all that follows asks of k is its side of M
    T = x.shape[0]
    Hq, Hkv = s["num_attention_heads"], s["num_key_value_heads"]
    D = s["hidden_size"] // Hq
    eps = s["layer_norm_eps"]
    n = w["norms"]

    h = layer_norm(x, n[0, 0], n[0, 1], eps)
    if k <= M:
        out, y = mamba(h, w, s)
        m = y if k == M else m
    else:
        out = (m * jax.nn.silu(h @ g_in)) @ w["mix_out"]
    x = x + out
    x = x + mlp(layer_norm(x, n[0, 2], n[0, 3], eps), w["fc1"][0], w["fc2"][0])

    h = layer_norm(x, n[1, 0], n[1, 1], eps)
    q = (h @ w["wq"] + w["bq"]).reshape(T, Hq, D)
    if k <= M:
        kk, vv = jnp.split(h @ w["wkv"] + w["bkv"], 2, axis=-1)
        own = (kk.reshape(T, Hkv, D), vv.reshape(T, Hkv, D))
        kv = own if k == M else kv
    else:
        own = kv
    x = x + diff_attention(q, own[0], own[1], w, l_odd, s["sliding_window"] if k < M else None, s)
    x = x + mlp(layer_norm(x, n[1, 2], n[1, 3], eps), w["fc1"][1], w["fc2"][1])
    return x, m, kv


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``."""
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        P = sizes["num_hidden_layers"] // 2
        M = P // 2
        # One program for each side of the middle pair, not one a pair.
        step = jax.jit(lambda x, m, kv, w, g_in, k, where: pair(x, m, kv, w, g_in, k, where, sizes),
                       static_argnums=(6,))
        m = kv = None
        for k in range(P):
            g_in = layer(k - M - 1)["g_in"] if k > M else None
            x, m, kv = step(x, m, kv, layer(k), g_in, jnp.float32(k), (k > M) - (k < M))
        x = layer_norm(x, final_norm[0], final_norm[1], sizes["layer_norm_eps"])
        return jax.jit(lambda x, head: x @ head)(x, head)
