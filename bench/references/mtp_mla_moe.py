"""Plain reference of the openPangu-Ultra-MoE decoder block (``model_type``
``pangu_ultra_moe``) AND of its next-token module: the DeepSeek-V3 shape (a
latent attention with a query bottleneck under plain rotary, leading dense
layers before expert layers, a sigmoid router and an always-on shared expert)
with a norm on each sublayer's OUTPUT, over one chip's share of the experts and
of the vocabulary.

Written from the model's published ``config.json``
(FreedomIntelligence/openPangu-Ultra-MoE-718B) and, for what the config has no
key for (marked +, each in the configuration's ``assumed``), from the Pangu
Ultra MoE report (arXiv:2505.04519) and the DeepSeek-V3 report and modelling
code, whose key names the config uses (arXiv:2412.19437; section 2.2 for the
module). T tokens, hidden C:

    x       = E[tok]                                                   [T, C]
  layer l:
    x       = x + RMSNorm(Attn(RMSNorm(x; attn_norm)); post_attn_norm) +  (sandwich_norm: the OUTPUT normed)
    x       = x + RMSNorm(FFN(RMSNorm(x; the FFN's input norm)); post_ffn_norm) +
  Attn(a), heads Hq, no bias:
    c_q     = RMSNorm(a @ wdq; q_a_norm);  [q_nope (qk_nope_head_dim), q_rope (qk_rope_head_dim)] = c_q @ wuq, a head
    [c, r]  = a @ wdkv;  c = RMSNorm(c; kv_a_norm) (kv_lora_rank);  r: ONE rotary key (qk_rope_head_dim) for all heads
    q_rope, r = rotate-half rotary +, inverse frequencies rope_theta^(-2j/D), no scaling
    [k_nope (qk_nope_head_dim), v (v_head_dim)] = c @ wukv, a head
    s       = (q_nope . k_nope + q_rope . r) / sqrt(qk_nope_head_dim + qk_rope_head_dim), causal softmax
    Attn    = concat_heads(softmax(s) v) @ wo
  FFN, l < first_k_dense_replace:  (silu(h @ wg) * (h @ wu)) @ wd of width intermediate_size
  FFN, else:
    s = sigmoid(h @ router) over ``routed_experts``; the chosen: the num_experts_per_tok largest of
    s + router_bias + (the config names no selection bias, no groups: the leaf is zeros); w = s[chosen];
    w = w / (sum(w) + 1e-20) (norm_topk_prob); w = w * routed_scaling_factor;
    FFN = shared(h) + sum over the chosen AND HELD of w_e expert_e(h), each a SwiGLU of moe_intermediate_size
    (held: experts 0 .. n_routed_experts - 1 of the ``routed_experts`` published; what a chosen expert that is not held
    would add is left out, as on the chip that serves this share)
  then a final RMSNorm and an untied head over the HELD rows of the vocabulary (the first vocab_size).

  The next-token module (num_nextn_predict_layers 1), for positions i = 0 .. T - 2, whose next token is known,
  h_i the last layer's x BEFORE the final norm +:
    u_i     = [RMSNorm(E[tok_{i+1}]; enorm) ; RMSNorm(h_i; hnorm)] @ eh_proj +   (the embedding's half first)
    u       = one layer of the expert layers' kind over u (positions i, causal over u), with its own weights
    logits  = RMSNorm(u; the module's final norm) @ head +              (embedding and head the model's own)
  row i is the module's distribution of tok_{i+2}: the draft a serving round verifies.

``jax.numpy``, float32, matmul precision "highest", one sequence, the whole
context at once: no cache, no pages, no absorption, no batching. Imports nothing
from the program under test.

Departures, for memory alone: attention runs a block of queries at a time; the
logits' rows are computed as they are read; ``forward`` waits for each layer
before it asks for the next one's weights.

The program keeps the two kinds of layer as two stacks in ``params["layers"]``
(``afmoe.py``'s docstring): this file asks ``layer(l)`` for dense layer l and
``layer(l - first_k_dense_replace)`` for an expert layer, and reads only the
stack it asked for. The module's leaves are not in ``layers``: ``module_forward``
takes them as a dict (the block's under the expert layers' names, beside
``enorm``, ``hnorm``, ``eh_proj`` and ``final_norm``).

Switches of ``sizes`` (``assumed_mechanisms`` in the configuration's file, all
as the model has them where absent), so that a control can run this file with
ONE mechanism changed and show that the comparison deciding ``correct`` sees it
(``hack/pangu_correct_control.py``): ``output_norms`` False drops both sublayers'
output norms (x + F(norm x)); ``absent_experts`` "computed" computes a chosen
expert that is not held all the same, with the weights of the held expert at its
index modulo the share; ``own_key`` False hides from every query the key at its
OWN position (it reads the cache as the query one position in front of it would:
what a round's second query does if it is given the first's length; position 0
still sees itself); ``head_first_row`` n reads token id t's logit from column
(t + n) modulo the held rows: a slice of the vocabulary that starts n rows
further on.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location("bench_reference_mhc_mla_moe", Path(__file__).with_name("mhc_mla_moe.py"))
latent = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(latent)
sparse, dense = latent.sparse, latent.dense

ATTENTION = ("attn_norm", "wdq", "q_a_norm", "wuq", "wdkv", "kv_a_norm", "wukv", "wo", "post_attn_norm", "post_ffn_norm")
DENSE_LEAVES = tuple("dense_" + n for n in ATTENTION + ("mlp_norm", "wg", "wu", "wd"))
EXPERT_LEAVES = ATTENTION + ("moe_norm", "router", "router_bias", "wg", "wu", "wd", "shared_wg", "shared_wu", "shared_wd")
MODULE_LEAVES = ("enorm", "hnorm", "eh_proj", "final_norm")
LEAVES = EXPERT_LEAVES + DENSE_LEAVES
OPTIONAL_LEAVES = ()
QUERY_BLOCK = 256
MECHANISMS = {"output_norms": True, "absent_experts": "left_out", "own_key": True, "head_first_row": 0}


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "vocab_size", "rope_theta", "rms_norm_eps",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts", "routed_experts", "num_experts_per_tok",
        "first_k_dense_replace", "n_shared_experts", "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_nextn_predict_layers")}
    if not model.get("sandwich_norm") or not model.get("norm_topk_prob") or model.get("rope_scaling") is not None:
        raise ValueError("this reference is the block with normed sublayer outputs, normalised routing weights and "
                         "plain rotary")
    if model.get("attention_bias") or model.get("hidden_act", "silu") != "silu" or model.get("tie_word_embeddings"):
        raise ValueError("this reference has no attention bias, SwiGLU blocks and an untied head")
    if any(model.get(k) not in (None, 1) for k in ("n_group", "topk_group")) or model.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference routes by sigmoid scores over one group")
    if not 0 < out["n_routed_experts"] <= out["routed_experts"]:
        raise ValueError(f"n_routed_experts {out['n_routed_experts']} held of routed_experts {out['routed_experts']}")
    if out["num_nextn_predict_layers"] != 1:
        raise ValueError("this reference has ONE next-token module")
    chosen = {**MECHANISMS, **(model.get("assumed_mechanisms") or {})}
    if set(chosen) - set(MECHANISMS) or chosen["absent_experts"] not in ("left_out", "computed"):
        raise ValueError(f"assumed_mechanisms {chosen}: the switches are {MECHANISMS}, absent_experts left_out or computed")
    out.update(chosen)
    return out


def attention(q, k, v, scale, own_key: bool):
    """Causal softmax attention a block of ``QUERY_BLOCK`` queries at a time: q, k
    [T, H, Dk], v [T, H, Dv], one key head a query head. ``own_key`` False: a
    query sees the keys in FRONT of its position alone (position 0 still itself)."""
    T, H, _ = q.shape
    n = -(-T // QUERY_BLOCK)
    key_pos = jnp.arange(T)

    def one(args):
        qb, pos = args
        horizon = pos if own_key else jnp.maximum(pos - 1, 0)
        scores = jnp.einsum("qhd,shd->hqs", qb, k) * scale
        probs = jax.nn.softmax(jnp.where((key_pos[None, :] <= horizon[:, None])[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v)

    pos = jnp.minimum(jnp.arange(n * QUERY_BLOCK), T - 1).reshape(n, QUERY_BLOCK)
    qb = jnp.pad(q, ((0, n * QUERY_BLOCK - T), (0, 0), (0, 0))).reshape(n, QUERY_BLOCK, H, q.shape[-1])
    return jax.lax.map(one, (qb, pos)).reshape(n * QUERY_BLOCK, H, v.shape[-1])[:T]


def latent_attention(a, w, positions, sizes):
    """Attn(a): a [T, C] the block's normed input."""
    T, eps = a.shape[0], sizes["rms_norm_eps"]
    Hq, Dn, Dr, Dv = (sizes[k] for k in ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rank = sizes["kv_lora_rank"]
    inv_freq = 1.0 / (sizes["rope_theta"] ** (jnp.arange(0, Dr, 2, dtype=jnp.float32) / Dr))
    q = (dense.rms_norm(a @ w["wdq"], w["q_a_norm"], eps) @ w["wuq"]).reshape(T, Hq, Dn + Dr)
    down = a @ w["wdkv"]
    c = dense.rms_norm(down[:, :rank], w["kv_a_norm"], eps)
    r = latent.rotary(down[:, None, rank:], positions, inv_freq)                       # [T, 1, Dr]
    up = (c @ w["wukv"]).reshape(T, Hq, Dn + Dv)
    qq = jnp.concatenate([q[..., :Dn], latent.rotary(q[..., Dn:], positions, inv_freq)], axis=-1)
    kk = jnp.concatenate([up[..., :Dn], jnp.broadcast_to(r, (T, Hq, Dr))], axis=-1)
    return attention(qq, kk, up[..., Dn:], (Dn + Dr) ** -0.5, sizes["own_key"]).reshape(T, Hq * Dv) @ w["wo"]


def route(h, w, sizes):
    """Routing weights [T, k] and the chosen experts [T, k] among ALL the routed."""
    scores = jax.nn.sigmoid(h @ w["router"])
    _, idx = jax.lax.top_k(scores + w["router_bias"], sizes["num_experts_per_tok"])
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    return gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"], idx


def block(x, w, positions, sizes, is_dense):
    """One decoder layer. ``w``: float32 matrices [in, out] and vectors, under
    the expert layers' names (a dense layer's with ``dense_`` taken off)."""
    eps = sizes["rms_norm_eps"]
    out = (lambda y, gain: dense.rms_norm(y, gain, eps)) if sizes["output_norms"] else (lambda y, gain: y)
    x = x + out(latent_attention(dense.rms_norm(x, w["attn_norm"], eps), w, positions, sizes), w["post_attn_norm"])
    if is_dense:
        f = latent.swiglu(dense.rms_norm(x, w["mlp_norm"], eps), w["wg"], w["wu"], w["wd"])
    else:
        h = dense.rms_norm(x, w["moe_norm"], eps)
        if w["router"].shape[-1] != sizes["routed_experts"] or w["wg"].shape[0] != sizes["n_routed_experts"]:
            raise ValueError(f"a router {w['router'].shape} over {w['wg'].shape[0]} held experts: not "
                             f"{sizes['n_routed_experts']} of {sizes['routed_experts']}")
        gate, idx = route(h, w, sizes)
        if sizes["absent_experts"] == "computed":
            idx = idx % sizes["n_routed_experts"]
        f = sparse.experts(h, gate, idx, w)        # an expert that is not held matches no index: left out
        if sizes["n_shared_experts"]:
            f = f + latent.swiglu(h, w["shared_wg"], w["shared_wu"], w["shared_wd"])
    return x + out(f, w["post_ffn_norm"])


def stream(token_ids, embed, layer, sizes):
    """The last layer's x [T, C], before the final norm: what the head's norm
    and the next-token module read."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(token_ids))
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        step = jax.jit(lambda x, w, is_dense: block(x, w, positions, sizes, is_dense), static_argnums=(2,))
        lead = sizes["first_k_dense_replace"]
        for i in range(sizes["num_hidden_layers"]):
            if i < lead:
                w = {n[len("dense_"):]: a for n, a in layer(i).items() if n in DENSE_LEAVES}
            else:
                w = {n: a for n, a in layer(i - lead).items() if n in EXPERT_LEAVES}
            x = jax.block_until_ready(step(x, w, i < lead))
            del w
        return x


def _head(head, sizes):
    """The head's columns as token ids read them (``head_first_row``)."""
    return jnp.roll(head, -sizes["head_first_row"], axis=1) if sizes["head_first_row"] else head


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``,
    the rows computed as they are read."""
    with jax.default_matmul_precision("highest"):
        x = stream(token_ids, embed, layer, sizes)
        return sparse._Logits(dense.rms_norm(x, final_norm, sizes["rms_norm_eps"]), _head(head, sizes))


def module_forward(token_ids, last_stream, embed, module, head, sizes):
    """The next-token module's logits [T - 1, V]: row i, computed from position
    i's stream and token i + 1, is its distribution of token i + 2. ``module``:
    float32 leaves, the block's under the expert layers' names beside
    ``MODULE_LEAVES``; ``last_stream`` [T, C] is ``stream``'s result."""
    with jax.default_matmul_precision("highest"):
        eps = sizes["rms_norm_eps"]
        ids = jnp.asarray(token_ids)
        e = dense.rms_norm(embed[ids[1:]].astype(jnp.float32), module["enorm"], eps)
        h = dense.rms_norm(last_stream[:-1], module["hnorm"], eps)
        u = jnp.concatenate([e, h], axis=-1) @ module["eh_proj"]
        w = {n: module[n] for n in EXPERT_LEAVES}
        u = jax.jit(lambda u, w: block(u, w, jnp.arange(u.shape[0]), sizes, False))(u, w)
        return sparse._Logits(dense.rms_norm(u, module["final_norm"], eps), _head(head, sizes))
