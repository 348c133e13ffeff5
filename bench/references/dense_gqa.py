"""Plain reference of the dense decoder block Mistral-7B and Qwen2.5-7B share.

Written from the published descriptions (Llama / Mistral 7B / Qwen2 technical
reports and the models' ``modeling_*.py``): pre-norm residual blocks of
RMSNorm -> grouped-query attention with rotary embeddings (optional q/k/v bias,
optional sliding window) -> RMSNorm -> gated SiLU MLP; a final RMSNorm and an
untied (or tied) output head. ``jax.numpy``, float32, matmul precision
"highest", one sequence, the whole context at once: no cache, no kernels, no
batching. Imports nothing from the program under test.

Weights are data. ``layer(i)`` hands over layer i's matrices already in
float32, laid out ``[in, out]``, so the caller can dequantise one layer at a
time and a 7B model never sits on the device in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rotary(x, positions, theta):
    """Rotate-half rotary embedding, as in the reference implementations:
    x is [T, heads, D]; pairs are (i, i + D/2)."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]     # [T, D/2]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(q, k, v, window):
    """Causal grouped-query attention over one sequence. q [T, Hq, D], k and v
    [T, Hkv, D]. With a window w, position t sees positions t-w+1 .. t."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(D))
    t = jnp.arange(T)
    mask = t[None, :] <= t[:, None]
    if window:
        mask = mask & (t[None, :] > t[:, None] - window)
    scores = jnp.where(mask[None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v)


def block(x, w, positions, sizes):
    """One decoder layer. ``w``: float32 matrices [in, out] and vectors."""
    Hq, Hkv, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    T = x.shape[0]
    h = rms_norm(x, w["attn_norm"], sizes["rms_norm_eps"])
    q, k, v = h @ w["wq"], h @ w["wk"], h @ w["wv"]
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rotary(q.reshape(T, Hq, D), positions, sizes["rope_theta"])
    k = rotary(k.reshape(T, Hkv, D), positions, sizes["rope_theta"])
    a = attention(q, k, v.reshape(T, Hkv, D), sizes.get("sliding_window"))
    x = x + a.reshape(T, Hq * D) @ w["wo"]
    h = rms_norm(x, w["mlp_norm"], sizes["rms_norm_eps"])
    return x + (jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"]


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence. ``embed`` [V, H]; ``layer(i)`` returns
    layer i's weights; ``head`` [H, V] (the caller passes ``embed.T`` when the
    configuration ties them)."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(token_ids))
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        step = jax.jit(lambda x, w: block(x, w, positions, sizes))
        for i in range(sizes["num_hidden_layers"]):
            x = step(x, layer(i))
        x = rms_norm(x, final_norm, sizes["rms_norm_eps"])
        return jax.jit(lambda x, head: x @ head)(x, head)
