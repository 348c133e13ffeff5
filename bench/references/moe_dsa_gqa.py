"""Plain reference of the Keye-VL-2.0 text decoder block: grouped-query
attention over the tokens a learned indexer chooses (the lightning indexer of
DeepSeek-Sparse-Attention), q/k norms, multimodal rotary, and a sparse mixture
of small SiLU experts in place of the MLP.

Written from the model's published ``config.json`` (Kwai-Keye/
Keye-VL-2.0-30B-A3B) and the description of its family. Layer ``l``, for the
token at position t (items marked + are assumed, below):

    h       = RMSNorm(x; attn_norm)
    q,k,v   = h @ wq [Hq, D], h @ wk [Hkv, D], h @ wv [Hkv, D]        no bias
    q, k    = RMSNorm over D (q_norm, k_norm) +, then M-RoPE: pair i of the
              D/2 rotates by the position row its mrope_section names
              ([16, 24, 24]: temporal, height, width); a text token's three
              positions are equal, which makes it plain rotary
    qI      = rotary(h @ wq_idx) [Hi, Di] +    kI = rotary(LayerNorm(h @ wk_idx)) [Di] +
    wI      = (h @ ww_idx) [Hi] * Hi ** -0.5 * Di ** -0.5 +
    I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s]),    s <= t
    S_t     = the min(topk, t + 1) largest I[t, s]; equal scores: lower s first
    a_t     = sum over s in S_t of softmax_s(q_t . k_s / sqrt(D)) v_s     (GQA)
    x'      = x + a @ wo
    m       = RMSNorm(x'; moe_norm);  r = m @ router [E]
    chosen  = the num_experts_per_tok largest of r;  p = softmax over those
              logits (softmax over all E, top-k, renormalised by
              norm_topk_prob: the same numbers)
    x_next  = x' + sum over chosen e of p_e * (silu(m @ wg[e]) * (m @ wu[e])) @ wd[e]

then a final RMSNorm and an untied head. ``jax.numpy``, float32, matmul
precision "highest", one sequence, the whole context at once: no cache, no
kernels, no pages. Imports nothing from the program under test.

Assumed (+), each in the configuration's ``assumed``: the config gives the
indexer's sizes (``sa_config``) and no equations, so they are the lightning
indexer's: per-head relu scores weighted by a learned projection of the same
input, one key a token shared by the heads, the key LayerNormed (eps 1e-6,
weight and bias), both scales folded into the weights, rotate-half rotary over
the indexer's own Di with the model's theta (temporal positions); q_norm and
k_norm as the family's other members have them; ``q_chunk_size`` /
``kv_chunk_size`` tile the indexer's score computation and change no number.
The vision tower is not here.

Departures, for memory alone: scores, selection and attention run over blocks
of ``QUERY_BLOCK`` queries (every query still sees every earlier key; the
selection is a stable descending sort of each query's scores, so no top-k
primitive's tie rule is relied on), and ``forward`` returns its logits lazily:
``bench/reference.py`` reads ``logits[n - 1:]``, and at 9,000 tokens the
whole [T, 151936] float32 array is 5.5 GB, which does not fit beside the
model; ``_Logits`` multiplies by the head only the rows that are asked for.
An expert a token did not choose gets the weight 0 for it, so running every
expert over every token and summing is the chosen experts' weighted sum.

Weights are data, as in ``dense_gqa.py``: ``layer(i)`` hands over layer i's
leaves in float32, matrices ``[in, out]``, the experts' stacked ``[E, in,
out]``.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location("bench_reference_dense_gqa",
                                               Path(__file__).with_name("dense_gqa.py"))
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)

LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
          "wq_idx", "wk_idx", "ww_idx", "k_idx_norm", "k_idx_bias",
          "moe_norm", "router", "wg", "wu", "wd")
OPTIONAL_LEAVES = ()
QUERY_BLOCK = 256
LAYER_NORM_EPS = 1e-6


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size", "rope_theta", "rms_norm_eps", "moe_intermediate_size",
        "num_experts", "num_experts_per_tok")}
    sa = model["sa_config"]
    out.update(indexer_num_heads=sa["indexer_num_heads"], indexer_head_dim=sa["indexer_head_dim"],
               topk=sa["topk"])
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("this reference is the one-key-a-token indexer")
    out["mrope_section"] = tuple((model.get("rope_scaling") or {}).get("mrope_section") or ())
    if out["mrope_section"] and sum(out["mrope_section"]) * 2 != out["head_dim"]:
        raise ValueError(f"mrope_section {out['mrope_section']} does not cover head_dim {out['head_dim']}")
    if not model.get("norm_topk_prob", True):
        raise ValueError("this reference renormalises the chosen experts' weights")
    return out


def mrope(x, positions3, theta, section):
    """Multimodal rotary: x [T, heads, D]; positions3 [3, T] (temporal, height,
    width); pair i of the D/2 takes the row its section names. Rotate-half
    pairing (i, i + D/2) as ``dense.rotary``."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angles3 = positions3.astype(jnp.float32)[:, :, None] * inv_freq[None, None, :]       # [3, T, D/2]
    row = np.repeat(np.arange(len(section)), section)                                    # [D/2]
    angles = jnp.take_along_axis(angles3, jnp.asarray(row)[None, None, :], axis=0)[0]    # [T, D/2]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def layer_norm(x, weight, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) * weight + bias


def indexer(h, w, positions, sizes):
    """Queries [T, Hi, Di], keys [T, Di], head weights [T, Hi]."""
    T = h.shape[0]
    Hi, Di = sizes["indexer_num_heads"], sizes["indexer_head_dim"]
    qi = dense.rotary((h @ w["wq_idx"]).reshape(T, Hi, Di), positions, sizes["rope_theta"])
    ki = dense.rotary(layer_norm(h @ w["wk_idx"], w["k_idx_norm"], w["k_idx_bias"])[:, None, :],
                      positions, sizes["rope_theta"])[:, 0]
    return qi, ki, (h @ w["ww_idx"]) * (Hi ** -0.5 * Di ** -0.5)


def selection(qi, ki, wi, q_pos, topk):
    """[Q, T] bool: for each query (at position q_pos) the min(topk, q_pos + 1)
    keys its indexer scores highest, equal scores from the lower position."""
    scores = jnp.einsum("qh,qhs->qs", wi, jax.nn.relu(jnp.einsum("qhd,sd->qhs", qi, ki)))
    visible = jnp.arange(ki.shape[0])[None, :] <= q_pos[:, None]
    order = jnp.argsort(jnp.where(visible, -scores, jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)                    # a key's place in its query's descending order
    return (rank < topk) & visible


def sparse_attention(q, k, v, qi, ki, wi, topk):
    """Causal grouped-query attention of q [T, Hq, D] over the keys each query's
    indexer chose, a block of queries at a time. k, v [T, Hkv, D]."""
    T, Hq, D = q.shape
    group = Hq // k.shape[1]
    kk, vv = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    n = -(-T // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - T
    blocks = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (n, QUERY_BLOCK) + a.shape[1:])

    def one(args):
        qb, qib, wib, pos = args
        mask = selection(qib, ki, wib, pos, topk)
        scores = jnp.einsum("qhd,shd->hqs", qb, kk) / jnp.sqrt(jnp.float32(D))
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, vv)

    # Padded queries sit at position T - 1: they see keys, so no row is empty.
    pos = jnp.minimum(jnp.arange(n * QUERY_BLOCK), T - 1).reshape(n, QUERY_BLOCK)
    out = jax.lax.map(one, (blocks(q), blocks(qi), blocks(wi), pos))
    return out.reshape(n * QUERY_BLOCK, Hq, D)[:T]


def experts(m, gate, idx, w):
    """The mixture for tokens m [T, H] under routing weights gate [T, k] of the
    experts idx [T, k]: one expert at a time over every token."""
    def one(out, e):
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)            # [T]
        y = (jax.nn.silu(m @ w["wg"][e]) * (m @ w["wu"][e])) @ w["wd"][e]
        return out + weight[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(w["wg"].shape[0]))
    return out


def block(x, w, positions, sizes):
    """One decoder layer. ``w``: float32 matrices [in, out] and vectors."""
    Hq, Hkv, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    T = x.shape[0]
    eps = sizes["rms_norm_eps"]
    h = dense.rms_norm(x, w["attn_norm"], eps)
    q = dense.rms_norm((h @ w["wq"]).reshape(T, Hq, D), w["q_norm"], eps)
    k = dense.rms_norm((h @ w["wk"]).reshape(T, Hkv, D), w["k_norm"], eps)
    positions3 = jnp.stack([positions] * 3)            # a text token: temporal = height = width
    section = sizes["mrope_section"] or (D // 2,)
    q = mrope(q, positions3, sizes["rope_theta"], section)
    k = mrope(k, positions3, sizes["rope_theta"], section)
    a = sparse_attention(q, k, (h @ w["wv"]).reshape(T, Hkv, D), *indexer(h, w, positions, sizes),
                         sizes["topk"])
    x = x + a.reshape(T, Hq * D) @ w["wo"]
    m = dense.rms_norm(x, w["moe_norm"], eps)
    chosen, idx = jax.lax.top_k(m @ w["router"], sizes["num_experts_per_tok"])
    return x + experts(m, jax.nn.softmax(chosen, axis=-1), idx, w)


class _Logits:
    """Logits [T, V] whose rows are computed when they are read: ``[a:]``,
    ``[a:b]`` or ``[i]`` multiplies those rows of the normed stream by the head."""

    def __init__(self, x, head):
        self._x, self._head = x, head
        self.shape = (x.shape[0], head.shape[1])

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda x, head: x @ head)(self._x[rows], self._head)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self[:], dtype=dtype)


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``,
    the rows computed as they are read (``_Logits``)."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(token_ids))
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        step = jax.jit(lambda x, w: block(x, w, positions, sizes))
        for i in range(sizes["num_hidden_layers"]):
            x = step(x, layer(i))
        return _Logits(dense.rms_norm(x, final_norm, sizes["rms_norm_eps"]), head)
