"""Plain reference of the Kimi-Linear decoder (``model_type`` ``kimi_linear``):
Kimi Delta Attention (KDA) layers beside a few latent-attention (MLA) layers
with no position encoding in one stack, a dense MLP in the first layer and, in
every other, experts under a sigmoid router beside a shared expert; an untied
head. ONE of the two chips that share each layer: the experts this file's
weights hold are a SHARE of those the router chooses among.

Written from the model's published ``config.json``
(moonshotai/Kimi-Linear-48B-A3B-Instruct) and, for what the config has no key
for (marked +, each in the configuration's ``assumed``), the family's modelling
code and its report (arXiv:2510.26692). T tokens, hidden H. Pre-norm blocks,
eps rms_norm_eps; layers are 1-indexed in the config:

    x       = E[tok]
  layer l:
    h       = RMSNorm(x; attn_norm)
    where l is in linear_attn_config.kda_layers (heads Hh of D = head_dim, P = Hh D, K = short_conv_kernel_size):
      q,k,v   = split(silu(conv(h @ kda_qkv)))      kda_qkv [H, 3 P] = [Wq, Wk, Wv] +; conv depthwise, causal,
                c_t = sum_{j=0..K-1} conv_w[j] * u_{t-K+1+j}, no bias +, u before the sequence's start zero
      q       = l2norm(q) * D^-0.5 ;  k = l2norm(k)    a head +; l2norm(a) = a / sqrt(sum a^2 + 1e-6) +
      g_t     = -exp(A_log[h]) * softplus((h @ kda_fa) @ kda_fb + dt_bias) +     a VECTOR a head: one log decay a channel
      beta_t  = sigmoid(h @ kda_b) +                                              one scalar a head
      S'      = Diag(exp(g_t)) S_{t-1} ;  u_t = beta_t (v_t - S'^T k_t) ;  S_t = S' + k_t u_t^T      S [D key, D value], S_0 = 0
      o_t     = S_t^T q_t
      o       = ((kda_norm * rmsnorm_D(o)) * sigmoid((h @ kda_ga) @ kda_gb + kda_gbias)) @ kda_out +
    where l is in linear_attn_config.full_attn_layers:
      q       = h @ wq [Hq, nope + rope]             no query bottleneck (q_lora_rank null)
      c, k_r  = split(h @ wdkv) [kv_lora_rank + rope] ;  c = RMSNorm(c; kv_a_norm)
      k_n, v  = split(c @ wukv) [Hq, nope + v]
      o       = softmax((nope + rope)^-0.5 (q_n k_n^T + q_r k_r^T) + causal) v @ wo     NO rotary anywhere + (mla_use_nope)
    x       = x + o
    m       = RMSNorm(x; mlp_norm | moe_norm)
    f       = layer l <= first_k_dense_replace:  swiglu(m) of width intermediate_size
              else  shared(m) + sum over the chosen e THAT ARE HELD of w_e * expert_e(m)
              chosen: the num_experts_per_token largest of sigmoid(m @ router) + router_bias + over ALL the
              routed experts (one group); w = the chosen scores over the sum of ALL the chosen ones
              (moe_renormalize) times routed_scaling_factor; held: experts 0 .. num_experts - 1 of the
              ``routed_experts`` published; experts and the shared expert swiglu of moe_intermediate_size
    x       = x + f

then a final RMSNorm and the untied head. The recurrence runs token by token
(``lax.scan``): no blocks, no solve, no cache, no kernels, no pages.
``jax.numpy``, float32, matmul precision "highest", one sequence from its
start. Imports nothing from the program under test.

The program keeps FOUR stacks in ``params["layers"]``, each under names of its
own: the KDA layers (``kda_qkv`` ... ``kda_out``), the MLA layers (``wq`` ...
``wo``), the leading dense layer (``dense_*``) and the expert layers' norms,
router, experts and shared expert. ``layer(i)`` slices EVERY declared leaf at
index i (past a stack's end the index is clamped): for layer l this file asks
for the layer's own leaves (at l, or at l less the dense layers) and for its
operator's at the layer's ORDINAL among its kind.

Four mechanisms are switches of ``sizes`` (``assumed_mechanisms`` in the
configuration's file, all as the model has them where absent), so that a control
can run this file with one of them changed and show that the comparison deciding
``correct`` sees it (``hack/kimi_correct_control.py``): ``delta_rule`` (False:
u_t = beta_t v_t, a decayed sum of outer products with no correction),
``decay_per_channel`` (False: a head's channels all take the mean of their log
decays: one decay a head), ``state_restart_every`` (n > 0: every KDA state is
zero in front of every n-th position: a state lost at every chunk boundary of n)
and ``absent_experts`` ("computed": a chosen expert that is not held is computed
all the same, with the weights of the held expert at its index modulo the share:
what a program that put something in place of the other chip's part would add).

Departures, for memory alone: attention runs a block of queries at a time; the
logits' rows are computed as they are read; ``forward`` runs a layer's mixer
and its FFN as two programs and waits for each before it asks for the next view
of the weights (``mamba2_moe_gqa.forward``'s reasons). An expert a token did not
choose gets the weight 0 for it.
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

KDA_LEAVES = ("kda_qkv", "conv_w", "kda_fa", "kda_fb", "dt_bias", "A_log", "kda_b", "kda_ga", "kda_gb", "kda_gbias",
              "kda_norm", "kda_out")
MLA_LEAVES = ("wq", "wdkv", "kv_a_norm", "wukv", "wo")
EXPERT_LEAVES = ("attn_norm", "moe_norm", "router", "router_bias", "wg", "wu", "wd", "shared_wg", "shared_wu", "shared_wd")
DENSE_LEAVES = ("dense_attn_norm", "dense_mlp_norm", "dense_wg", "dense_wu", "dense_wd")
LEAVES = KDA_LEAVES + MLA_LEAVES + EXPERT_LEAVES + DENSE_LEAVES
OPTIONAL_LEAVES = ()
MECHANISMS = {"delta_rule": True, "decay_per_channel": True, "state_restart_every": 0, "absent_experts": "left_out"}


def sizes(model: dict) -> dict:
    out = {k: model[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "vocab_size", "rms_norm_eps", "intermediate_size",
        "moe_intermediate_size", "num_experts", "routed_experts", "num_experts_per_token", "first_k_dense_replace",
        "num_shared_experts", "routed_scaling_factor", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim")}
    lin = model["linear_attn_config"]
    out.update(kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"], kda_conv=lin["short_conv_kernel_size"],
               kda_layers=tuple(lin["kda_layers"]), full_attn_layers=tuple(lin["full_attn_layers"]))
    every = sorted(out["kda_layers"] + out["full_attn_layers"])
    if every != list(range(1, out["num_hidden_layers"] + 1)):
        raise ValueError(f"kda_layers and full_attn_layers {every}: each of layers 1..{out['num_hidden_layers']} once")
    if (model.get("q_lora_rank") is not None or not model.get("mla_use_nope") or model.get("rope_scaling") is not None
            or model.get("tie_word_embeddings")):
        raise ValueError("this reference is the latent attention with no query bottleneck and no position encoding, "
                         "under an untied head")
    if (model.get("moe_router_activation_func") != "sigmoid" or not model.get("moe_renormalize")
            or model.get("num_expert_group", 1) != 1 or model.get("topk_group", 1) != 1 or model.get("moe_layer_freq", 1) != 1
            or model.get("hidden_act", "silu") != "silu"):
        raise ValueError("this reference routes by sigmoid scores over one group, normalised, in every layer behind "
                         "the dense ones, with SwiGLU blocks")
    if not 0 < out["num_experts"] <= out["routed_experts"]:
        raise ValueError(f"num_experts {out['num_experts']} held of routed_experts {out['routed_experts']}")
    chosen = {**MECHANISMS, **(model.get("assumed_mechanisms") or {})}
    if set(chosen) - set(MECHANISMS) or chosen["absent_experts"] not in ("left_out", "computed"):
        raise ValueError(f"assumed_mechanisms {chosen}: the switches are {MECHANISMS}, absent_experts left_out or computed")
    out.update(chosen)
    return out


def kda(h, w, sizes):
    """Kimi Delta Attention on h [T, H], one sequence from its start."""
    T = h.shape[0]
    Hh, D, K = sizes["kda_heads"], sizes["kda_head_dim"], sizes["kda_conv"]
    P = Hh * D
    u = h @ w["kda_qkv"]
    c = w["conv_w"][K - 1] * u
    for lag in range(1, K):
        c = c + w["conv_w"][K - 1 - lag] * jnp.concatenate([jnp.zeros((lag, u.shape[1]), u.dtype), u[:T - lag]])
    c = jax.nn.silu(c)
    q, k, v = (c[:, i * P:(i + 1) * P].reshape(T, Hh, D) for i in range(3))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * D ** -0.5, unit(k)
    g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus((h @ w["kda_fa"]) @ w["kda_fb"] + w["dt_bias"]).reshape(T, Hh, D)
    if not sizes["decay_per_channel"]:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(h @ w["kda_b"])
    restart = jnp.zeros((T,), bool)
    if sizes["state_restart_every"]:
        restart = jnp.arange(T) % sizes["state_restart_every"] == 0

    def step(S, tok):
        q_t, k_t, v_t, g_t, b_t, zero = tok
        S = jnp.exp(g_t)[:, :, None] * jnp.where(zero, 0.0, S)
        seen = jnp.sum(S * k_t[:, :, None], axis=1) if sizes["delta_rule"] else 0.0
        S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - seen))[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((Hh, D, D), jnp.float32), (q, k, v, g, beta, restart))
    gate = jax.nn.sigmoid((h @ w["kda_ga"]) @ w["kda_gb"] + w["kda_gbias"]).reshape(T, Hh, D)
    return (dense.rms_norm(o, w["kda_norm"], sizes["rms_norm_eps"]) * gate).reshape(T, P) @ w["kda_out"]


def mla(h, w, sizes):
    """The latent attention on h [T, H]: no query bottleneck, no rotary."""
    T = h.shape[0]
    Hq, Dn, Dr, Dv, rank = (sizes[k] for k in ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                                                "v_head_dim", "kv_lora_rank"))
    q = (h @ w["wq"]).reshape(T, Hq, Dn + Dr)
    down = h @ w["wdkv"]
    up = (dense.rms_norm(down[:, :rank], w["kv_a_norm"], sizes["rms_norm_eps"]) @ w["wukv"]).reshape(T, Hq, Dn + Dv)
    keys = jnp.concatenate([up[..., :Dn], jnp.broadcast_to(down[:, None, rank:], (T, Hq, Dr))], axis=-1)
    return latent.attention(q, keys, up[..., Dn:], (Dn + Dr) ** -0.5).reshape(T, Hq * Dv) @ w["wo"]


def route(m, w, sizes):
    """Routing weights [T, k] and the chosen experts [T, k] among ALL the routed."""
    scores = jax.nn.sigmoid(m @ w["router"])
    _, idx = jax.lax.top_k(scores + w["router_bias"], sizes["num_experts_per_token"])
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    return gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"], idx


def mixer_block(x, norm, op, sizes, is_kda):
    """A layer's first half: x + Mixer(RMSNorm(x))."""
    h = dense.rms_norm(x, norm, sizes["rms_norm_eps"])
    return x + (kda(h, op, sizes) if is_kda else mla(h, op, sizes))


def ffn_block(x, own, sizes, is_dense):
    """A layer's second half. ``own``: a dense layer's MLP, or an expert layer's
    norm, router, HELD experts and shared expert."""
    if is_dense:
        return x + latent.swiglu(dense.rms_norm(x, own["mlp_norm"], sizes["rms_norm_eps"]), own["wg"], own["wu"], own["wd"])
    m = dense.rms_norm(x, own["moe_norm"], sizes["rms_norm_eps"])
    if own["router"].shape[-1] != sizes["routed_experts"] or own["wg"].shape[0] != sizes["num_experts"]:
        raise ValueError(f"a router {own['router'].shape} over {own['wg'].shape[0]} held experts: not "
                         f"{sizes['num_experts']} of {sizes['routed_experts']}")
    gate, idx = route(m, own, sizes)
    if sizes["absent_experts"] == "computed":
        idx = idx % sizes["num_experts"]
    f = sparse.experts(m, gate, idx, own)      # an expert that is not held matches no index: left out
    if sizes["num_shared_experts"]:
        f = f + latent.swiglu(m, own["shared_wg"], own["shared_wu"], own["shared_wd"])
    return x + f


def forward(token_ids, embed, layer, final_norm, head, sizes):
    """Logits [T, V] for one sequence; the contract of ``dense_gqa.forward``,
    the rows computed as they are read. A layer runs in two programs, each with
    its own float32 view of the weights and nothing of the other's."""
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(token_ids)].astype(jnp.float32)
        mix = jax.jit(lambda x, norm, op, is_kda: mixer_block(x, norm, op, sizes, is_kda), static_argnums=(3,))
        ffn = jax.jit(lambda x, own, is_dense: ffn_block(x, own, sizes, is_dense), static_argnums=(2,))
        lead, ordinal = sizes["first_k_dense_replace"], {True: 0, False: 0}
        for i in range(sizes["num_hidden_layers"]):
            is_kda, is_dense = (i + 1) in sizes["kda_layers"], i < lead
            norm = layer(i)["dense_attn_norm"] if is_dense else layer(i - lead)["attn_norm"]
            mine = KDA_LEAVES if is_kda else MLA_LEAVES
            op = {n: a for n, a in layer(ordinal[is_kda]).items() if n in mine}
            ordinal[is_kda] += 1
            x = jax.block_until_ready(mix(x, norm, op, is_kda))
            del op
            if is_dense:
                own = {n[len("dense_"):]: a for n, a in layer(i).items() if n in DENSE_LEAVES}
            else:
                own = {n: a for n, a in layer(i - lead).items() if n in EXPERT_LEAVES}
            x = jax.block_until_ready(ffn(x, own, is_dense))
            del own
        return sparse._Logits(dense.rms_norm(x, final_norm, sizes["rms_norm_eps"]), head)
