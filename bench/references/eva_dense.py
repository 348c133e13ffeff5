"""Plain reference of EvaByte's decoder block: EVA attention in every layer.

Written from the model's description ("EvaByte: Efficient Byte-level Language
Models at Scale", HKU NLP and SambaNova, 2025; EVA is "Efficient Attention via
Control Variates", arXiv:2302.04542, in the deterministic form of the model's
``eva.py``) and its ``config.json``: pre-norm residual blocks of RMSNorm (weight
``1 + w``) -> attention with rotary embeddings and NO grouped heads -> RMSNorm ->
gated SiLU MLP, sums in float32; a final RMSNorm and an untied head of
``num_pred_heads`` heads of ``vocab_size`` columns side by side, the first the
next byte's.

EVA, per head h, with s = D ** -0.5, learned vectors mu_h and phi_h, windows of W
tokens (``window_size``) and chunks of C (``chunk_size``), w(t) = t // W:

    summary of chunk j:  a_m = softmax over m in chunk j of (s k_m . mu_h),  k~_j = sum_m a_m k_m
                         b_m = softmax over m in chunk j of (s k_m . phi_h), v~_j = sum_m b_m v_m
    query t sees:        the keys m of its OWN window with m <= t, exactly;
                         the summaries j of whole chunks of EARLIER windows: j < (W / C) w(t)
    out_t = one softmax over both sets: scores s q_t . k_m and s q_t . k~_j, values v_m and v~_j

Keys are rotated at their absolute positions BEFORE they are pooled. A chunk of
the query's own window is never seen as a summary, a token of an earlier window
never as itself.

``jax.numpy``, float32, matmul precision "highest", one sequence, explicit masks
over the whole context, queries in blocks so that 9k tokens fit: no cache, no
kernels, no batching. Imports nothing from the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "wg", "wu", "wd", "eva_mu", "eva_phi")
OPTIONAL_LEAVES = ()

# ``pooling``: a chunk's weights (the two softmaxes, or a plain mean); ``summaries``: which
# a query sees (those of earlier windows; none; its own window's complete chunks too, one
# window early; or, a roll that was missed, the first W / C EXACT rows of each earlier
# window in their place); ``precision``: float32 throughout (what a reference is), or one
# step below what the configuration serves at: ``bfloat16`` rounds the stream, the
# projections and the summaries where the served path holds them in bf16, ``e4m3`` rounds
# the int8 matrices again to 8-bit floats a column (``e4m3``).
MECHANISMS = {"pooling": "softmax", "summaries": "earlier_windows", "precision": "float32"}
ALLOWED = {"pooling": ("softmax", "mean"),
           "summaries": ("earlier_windows", "none", "one_window_early", "stale_rows"),
           "precision": ("float32", "bfloat16", "e4m3")}

QUERY_BLOCK = 512   # queries a block of scores: [heads, 512, T + T / C] float32 is 0.6 GB at 9k tokens


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps", "window_size", "chunk_size")}
    assert out["num_attention_heads"] == out["num_key_value_heads"], "EVA's vectors are a head's: no grouped heads"
    out["head_dim"] = model.get("head_dim") or out["hidden_size"] // out["num_attention_heads"]
    out["norm_add_unit_offset"] = bool(model.get("norm_add_unit_offset", False))
    # Three mechanisms are switches (``assumed_mechanisms`` in the configuration's file;
    # hack/eva_correct_control.py turns one at a time): what a program that lacked the
    # mechanism, or had it wrong, would compute.
    chosen = {**MECHANISMS, **(model.get("assumed_mechanisms") or {})}
    if set(chosen) != set(MECHANISMS) or any(chosen[k] not in allowed for k, allowed in ALLOWED.items()):
        raise ValueError(f"assumed_mechanisms {chosen}: the switches are {ALLOWED}")
    out.update(chosen)
    return out


def rms_norm(x, weight, sizes):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + sizes["rms_norm_eps"]) * (weight + 1.0 if sizes["norm_add_unit_offset"] else weight)


def rotary(x, positions, theta):
    """Rotate-half rotary embedding: x is [T, heads, D]; pairs are (i, i + D/2)."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def stored(x, sizes):
    """``x`` as the served path holds it between two operations: itself in a float32
    reference, rounded to bf16 and read back under ``precision`` bfloat16."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if sizes["precision"] == "bfloat16" else x


def summaries(k, v, mu, phi, sizes):
    """k~, v~ of every chunk: [T / C (rounded up), heads, D]. A last chunk short of C
    tokens is padded with zeros; it belongs to the last window, which no query
    sees as summaries."""
    T, H, D = k.shape
    C = sizes["chunk_size"]
    pad = -T % C
    kc = jnp.pad(k, ((0, pad), (0, 0), (0, 0))).reshape(-1, C, H, D)
    vc = jnp.pad(v, ((0, pad), (0, 0), (0, 0))).reshape(-1, C, H, D)
    if sizes["pooling"] == "mean":
        return jnp.mean(kc, axis=1), jnp.mean(vc, axis=1)
    s = D ** -0.5
    a = jax.nn.softmax(s * jnp.sum(kc * mu[None, None], axis=-1), axis=1)     # [chunks, C, heads]
    b = jax.nn.softmax(s * jnp.sum(kc * phi[None, None], axis=-1), axis=1)
    return jnp.sum(a[..., None] * kc, axis=1), jnp.sum(b[..., None] * vc, axis=1)


def eva_attention(q, k, v, mu, phi, sizes):
    """q, k, v [T, heads, D], rotated; mu, phi [heads, D]. Returns [T, heads, D]."""
    T, H, D = q.shape
    W, C = sizes["window_size"], sizes["chunk_size"]
    ks, vs = (stored(a, sizes) for a in summaries(k, v, mu, phi, sizes))
    if sizes["summaries"] == "stale_rows":
        # A roll that was missed: in place of window w's summaries, the first W / C
        # exact rows of that window (what the page held before the fold).
        first = (jnp.arange(ks.shape[0]) // (W // C)) * W + jnp.arange(ks.shape[0]) % (W // C)
        first = jnp.minimum(first, T - 1)
        ks, vs = k[first], v[first]
    s = D ** -0.5
    m = jnp.arange(T)
    j = jnp.arange(ks.shape[0])
    out = []
    for start in range(0, T, QUERY_BLOCK):
        t = jnp.arange(start, min(start + QUERY_BLOCK, T))
        window = t // W
        local = (m[None, :] // W == window[:, None]) & (m[None, :] <= t[:, None])
        seen = {"earlier_windows": window, "none": jnp.zeros_like(window),
                "one_window_early": window + 1, "stale_rows": window}[sizes["summaries"]]
        # (one window early: the query's own window's chunks as summaries too, those that are complete)
        remote = (j[None, :] < (W // C) * seen[:, None]) & (C * j[None, :] + C - 1 <= t[:, None])
        scores = jnp.concatenate([
            jnp.where(local[None], s * jnp.einsum("thd,mhd->htm", q[t], k), -jnp.inf),
            jnp.where(remote[None], s * jnp.einsum("thd,jhd->htj", q[t], ks), -jnp.inf)], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("htm,mhd->thd", probs[..., :T], v) + jnp.einsum("htj,jhd->thd", probs[..., T:], vs))
    return jnp.concatenate(out, axis=0)


def block(x, w, positions, sizes):
    """One decoder layer. ``w``: float32 matrices [in, out] and vectors."""
    H, D = sizes["num_attention_heads"], sizes["head_dim"]
    T = x.shape[0]
    r = lambda a: stored(a, sizes)
    h = r(rms_norm(x, w["attn_norm"], sizes))
    q = r(rotary(r(h @ w["wq"]).reshape(T, H, D), positions, sizes["rope_theta"]))
    k = r(rotary(r(h @ w["wk"]).reshape(T, H, D), positions, sizes["rope_theta"]))
    a = r(eva_attention(q, k, r(h @ w["wv"]).reshape(T, H, D), w["eva_mu"], w["eva_phi"], sizes))
    x = r(x + r(a.reshape(T, H * D) @ w["wo"]))
    h = r(rms_norm(x, w["mlp_norm"], sizes))
    return r(x + r(r(jax.nn.silu(r(h @ w["wg"])) * r(h @ w["wu"])) @ w["wd"]))


MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def e4m3(w):
    """A matrix [in, out] rounded to 8-bit floats (4 exponent bits, 3 of mantissa) under
    one scale an output column: the precision below the int8 the configuration serves."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence. ``embed`` [V, H]; ``layer(i)`` returns layer
    i's weights; ``head`` [H, V x prediction heads]: the first V columns are read."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(token_ids))
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        lower = sizes["precision"] == "e4m3"
        step = jax.jit(lambda x, w: block(x, {n: e4m3(a) if lower and n in MATRICES else a for n, a in w.items()},
                                          positions, sizes))
        for i in range(sizes["num_hidden_layers"]):
            x = step(x, layer(i))
        x = rms_norm(x, final_norm, sizes)
        return jax.jit(lambda x, head: x @ head[:, :sizes["vocab_size"]])(x, head)
