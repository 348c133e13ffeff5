"""Sparse-MoE decoders in pure JAX: the Mixtral block, the SmallThinker
block (ISSUE 28: the router reads the attention's normed input, ReGLU
experts, global NoPE layers beside windowed RoPE layers), the
Keye-VL-2.0 text block (ISSUE 36: q/k norms, and a learned sparse
attention, ops/sparse_attention.py, whose indexer keys are a third paged
array; it rides the attention seam of the shared skeletons), and the
Trinity-Mini block (ISSUE 41, ``afmoe``: leading dense layers before the
expert layers in one layer stack, a sigmoid router with a selection bias
and an always-on shared expert, gated attention under sandwich norms), and
the LFM2 block (ISSUE 43, ``lfm2_moe``: gated short convolutions beside a few
GQA layers in one stack, a per-layer KIND with a per-kind cache on the shared
skeletons; the sigmoid router with no shared expert), and the Xing4.0 block
(ISSUE 47, ``xing4_0``: a latent attention whose one pool of compressed rows
rides the attention seam as its owner, ops/mla_attention.py, and several
residual streams a token mixed by hyper-connections, ops/hyper_connections.py,
on the skeletons' residual seam; Trinity's dense prefix, sigmoid router and
shared expert), and the Granite-4.0-H block (ISSUE 50, ``granitemoehybrid``:
the per-layer kind's third value, a Mamba-2 mixer whose matrix state a head
rides the state store beside the convolution's tail, ops/ssd.py; the four
multipliers on the skeletons; a softmax router beside a shared MLP in every
layer), and the Kimi-Linear block (ISSUE 54, ``kimi_linear``: the kind's fourth
value, Kimi Delta Attention, ops/kda.py, a delta rule with a decay a channel
whose matrix state rides the state store; the latent attention, no query
bottleneck and no rotary, as the OTHER kind of layer, its pool of those layers
alone; a chip's SHARE of the experts under the whole router).

Same TPU-first skeleton as models/llama.py (stacked layers + lax.scan,
the shared paged and ragged forwards) with the dense MLP replaced by a
top-k mixture of experts (ops/moe.py). Expert weights carry a leading
expert axis; under a mesh with an ``ep`` axis it is sharded there.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from inference_gateway_tpu.models.family import INT8_ALONE, Family, attention_layers
from inference_gateway_tpu.models.llama import (
    LlamaConfig,
    _attn_out,
    _dense_ffn,
    _embed,
    _ffn_out,
    _head,
    _layer_groups,
    _rope_qk,
    _scaled,
    _scan_layers,
    forward_paged_impl,
    forward_ragged_impl,
    window_pages,
    windowed_layers,
)
from inference_gateway_tpu.ops import kda as kda_ops
from inference_gateway_tpu.ops.attention import causal_prefill_mask, decode_mask, gqa_attend
from inference_gateway_tpu.ops.moe import ACTIVATIONS, moe_dense, moe_dropless, router_sigmoid, router_topk
from inference_gateway_tpu.ops.norms import rms_norm
from inference_gateway_tpu.ops.quant import QTensor, qeinsum, qmatmul, seeded_qk_gain, seeded_std
from inference_gateway_tpu.ops.rope import apply_rope, rope_cos_sin, rope_inv_freq, yarn_mscale
from inference_gateway_tpu.ops.sparse_attention import one_query_tokens_read, sparse_decode, sparse_ragged
from inference_gateway_tpu.ops.ssd import block_plan, recurrence, ssd_chunk, state_update, update_store
from inference_gateway_tpu.ops.ssm import conv_decode, conv_ragged, token_segments


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    experts_per_token: int = 2
    # "dropless": ops/moe.moe_dropless, the serving layer. "dense": every
    # expert on every token, the tests' oracle. "capacity" is what the
    # benchmark's Mixtral fixture still calls the serving layer (the one
    # dropless replaced in PR 28) and means dropless.
    moe_impl: str = "dropless"
    # Which normed state the router reads: the experts' own input
    # ("ffn", Mixtral) or the attention's ("attn", SmallThinker).
    router_input: str = "ffn"
    # The learned sparse attention's indexer (ISSUE 36); indexer_topk 0:
    # none. Each query keeps the indexer_topk cached tokens its
    # indexer_heads heads of indexer_head_dim score highest; one indexer
    # key a token, shared by the heads.
    indexer_heads: int = 0
    indexer_head_dim: int = 64
    indexer_topk: int = 0
    # Trinity-Mini's block (ISSUE 41). The first num_dense_layers of the
    # num_layers have a dense gated MLP of dense_intermediate_size in place
    # of the experts: their leaves are a stack of their own (``dense_*``,
    # leading axis num_dense_layers) beside the expert layers' (leading
    # axis expert_layers); the layer index, the page pool and the page
    # table run over both.
    num_dense_layers: int = 0
    dense_intermediate_size: int = 0
    # "softmax": over the chosen logits (ops/moe.router_topk). "sigmoid":
    # sigmoid scores, chosen by score + the leaf ``router_bias``, weighed by
    # the scores alone, normalised, times route_scale (ops/moe.router_sigmoid).
    router_score: str = "softmax"
    route_scale: float = 1.0
    # Always-on experts beside the routed ones: ONE dense gated MLP of
    # shared_experts * intermediate_size (leaves ``shared_w*``), added once.
    shared_experts: int = 0
    # The normaliser's epsilon of the sigmoid router: w / (sum(w) + route_eps).
    route_eps: float = 1e-20
    # LFM2's block (ISSUE 43): a per-layer KIND, one 0/1 flag a layer or None.
    # 1 is a layer whose token mixer is a gated short convolution, y = W_out
    # (C * conv(B * u)) with [B, C, u] = h W_in, depthwise and causal over
    # conv_kernel taps: it writes no key and keeps, a slot, its last
    # conv_kernel - 1 values of B * u (the cache pytree's ``conv``). 0 is an
    # attention layer. Each operator's leaves are a stack of their own
    # (``conv_in``, ``conv_w``, ``conv_out``: leading axis conv_layers; ``wq``
    # ... ``k_norm``: leading axis cache_layers), read at the layer's ordinal
    # among its kind, as are the page pool's planes (cache_layers of them).
    # Granite-4.0-H's block (ISSUE 50): 2 is a layer whose token mixer is a
    # Mamba-2 mixer (ops/ssd.py): ssm_heads heads of ssm_head_dim, a float32
    # state of ssm_head_dim x ssm_state a head, B and C shared by all heads (one
    # group), a depthwise causal convolution of conv_kernel taps with bias and
    # SiLU over [x, B, C], a gated RMSNorm behind the scan. It keeps, a slot,
    # the state (``ssm``) and the convolution's tail (``conv``). A model holds
    # attention layers and ONE kind of state layer. ssd_block: the tokens a block
    # of the scan's blocked form holds (any value gives the recurrence's numbers).
    # Kimi-Linear's block (ISSUE 54): 3 is a layer whose token mixer is Kimi
    # Delta Attention (ops/kda.py): kda_heads heads of kda_head_dim, a float32
    # state of kda_head_dim x kda_head_dim a head decayed by a vector a head a
    # token and corrected by a rank-one delta rule; q, k and v each behind a
    # depthwise causal convolution of conv_kernel taps and SiLU. It keeps, a
    # slot, the state (``kda``) and the convolution's tail (``conv``).
    # kda_block: the tokens a block of its chunk form holds.
    layer_conv: tuple | None = None
    conv_kernel: int = 3
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssd_block: int = 256
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_block: int = 64
    kda_rank: int = 0   # the decay's and the gate's low-rank bottleneck; 0: kda_head_dim
    # A chip's share of the experts (ISSUE 54). 0: every expert is held.
    # Else the expert stacks hold experts_held of the num_experts, from expert
    # experts_first on; the router is num_experts wide and chooses among all;
    # a chosen expert that is not held is computed by NO ONE here (its chip
    # adds it): the layer's output is the held experts' part.
    experts_held: int = 0
    experts_first: int = 0
    # Xing4.0's block (ISSUE 47). kv_lora_rank 0: none. A latent attention
    # (the DeepSeek-V2 form): queries through a q_lora_rank bottleneck with a
    # norm, num_heads of qk_nope_head_dim + qk_rope_head_dim (= head_dim);
    # keys and values up from ONE normed latent of kv_lora_rank a token, beside
    # one rotary key of qk_rope_head_dim all heads share; values of v_head_dim.
    # The page pool is the one array ``kv``, a row [latent, rotary key, zeros
    # to the lane grid] a token a layer (``latent_row``); no ``k``, no ``v``.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # hc_mult 0: one residual stream. Else that many streams a token, mixed
    # around each block by manifold-constrained hyper-connections: leaves
    # hc_{attn,ffn}_{phi,alpha,bias}, float32 (ops/hyper_connections.py).
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)
    # openPangu-Ultra-MoE's next-token module (ISSUE 56; ``num_nextn_predict_layers``;
    # the DeepSeek-V3 report's section 2.2). 0: none. 1: behind the last layer one
    # further block of the expert layers' kind (leaves ``params["mtp"]``), its own
    # plane of the latent pool behind the layers' (``cache_layers`` counts it). For
    # a position i whose next token is known it reads W_eh [norm_e(Emb(t_{i+1}));
    # norm_h(h_i)], h_i the last block's stream BEFORE the final norm, and gives,
    # through its own final norm and the model's head, logits for t_{i+2}: the
    # draft a serving round verifies as a second query of the row (``mtp_ragged``).
    mtp_layers: int = 0
    # A chip's share of the vocabulary (ISSUE 56): ``vocab_size`` rows of the
    # embedding and columns of the head are HELD, the first of vocab_published
    # (0: all of them); ids, logits, sampling and logit_bias run over the slice.
    vocab_published: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.moe_impl not in ("dropless", "dense", "capacity"):
            raise ValueError(f"moe_impl {self.moe_impl!r}: dropless or dense")
        if self.router_input not in ("ffn", "attn"):
            raise ValueError(f"router_input {self.router_input!r}: ffn or attn")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score {self.router_score!r}: softmax or sigmoid")
        if not 0 <= self.num_dense_layers < self.num_layers:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} of {self.num_layers} layers")
        if self.layer_conv is not None:
            flags = tuple(int(f) for f in self.layer_conv)
            object.__setattr__(self, "layer_conv", flags)
            if (len(flags) != self.num_layers or 0 not in flags or len(set(flags) - {0}) != 1
                    or not set(flags) <= {0, 1, 2, 3}):
                raise ValueError(f"layer_conv {flags}: one of 0 (attention), 1 (short convolution), 2 (Mamba-2), 3 "
                                 f"(Kimi Delta Attention) a layer of {self.num_layers}; attention and ONE kind of "
                                 "state layer present")
            if (2 in flags) != bool(self.ssm_heads):
                raise ValueError("layer_conv 2 and ssm_heads: a Mamba-2 layer has heads, and no other layer")
            if (3 in flags) != bool(self.kda_heads):
                raise ValueError("layer_conv 3 and kda_heads: a Kimi Delta Attention layer has heads, and no other layer")
            if self.indexer_topk or self.attn_gate or self.sandwich_norm or self.qkv_bias:
                raise ValueError("layer_conv: the attention operators' stack carries no indexer, gate, post-norm or bias")
        if self.kv_lora_rank:
            if self.hd != self.qk_nope_head_dim + self.qk_rope_head_dim or not self.v_head_dim:
                raise ValueError("kv_lora_rank: head_dim is qk_nope_head_dim + qk_rope_head_dim, values of v_head_dim")
            if self.indexer_topk or self.sliding_window or self.qk_norm or self.attn_gate or self.qkv_bias:
                raise ValueError("kv_lora_rank: the latent attention carries no indexer, window, q/k norm, gate "
                                 "or bias")
            if self.layer_rope is not None and any(self.layer_rope):
                raise ValueError("kv_lora_rank: the shared lanes are rotated in every layer (layer_rope None) or in "
                                 "none (all 0)")
            if self.layer_conv is not None and (not self.scans_runs or self.hc_mult):
                raise ValueError("kv_lora_rank beside a per-layer kind: the state layers are scanned in runs "
                                 "(Mamba-2 or Kimi Delta Attention), one residual stream")
        if self.mtp_layers not in (0, 1) or (self.mtp_layers and (
                not self.kv_lora_rank or self.layer_conv is not None or self.hc_mult or self.tie_word_embeddings)):
            raise ValueError("mtp_layers: ONE next-token module, behind a latent attention's layers of one kind, one "
                             "residual stream, an untied head")
        if self.experts_held and not (0 < self.experts_held <= self.num_experts - self.experts_first
                                      and self.experts_first >= 0):
            raise ValueError(f"experts_held {self.experts_held} from {self.experts_first} of {self.num_experts} experts")

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def conv_layers(self) -> int:
        """Layers that keep a state a slot (a convolution's tail; a Mamba-2
        layer's matrix state too) and own no pages."""
        return sum(f > 0 for f in self.layer_conv or ())

    @property
    def mamba2(self) -> bool:
        """The model's state layers are Mamba-2 mixers (ISSUE 50)."""
        return 2 in (self.layer_conv or ())

    @property
    def kda(self) -> bool:
        """The model's state layers are Kimi Delta Attention (ISSUE 54)."""
        return 3 in (self.layer_conv or ())

    @property
    def scans_runs(self) -> bool:
        """The state store is too large to stand under a ``lax.cond``: the layers
        are scanned in runs of one kind (``llama._layer_groups``)."""
        return self.mamba2 or self.kda

    @property
    def kda_inner(self) -> int:
        """A Kimi Delta Attention layer's inner width: heads x head size."""
        return self.kda_heads * self.kda_head_dim

    @property
    def held_experts(self) -> int:
        """The experts the stacks hold: all of them, or this chip's share."""
        return self.experts_held or self.num_experts

    @property
    def ssm_inner(self) -> int:
        """A Mamba-2 mixer's inner width: heads x head size."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """What a state layer's convolution runs over: hidden_size for the
        gated short convolution, [x, B, C] for a Mamba-2 mixer."""
        if self.kda:   # q, k and v, side by side
            return 3 * self.kda_inner
        return self.ssm_inner + 2 * self.ssm_state if self.mamba2 else self.hidden_size

    @property
    def cache_layers(self) -> int:
        """Layers that write the page pool, its leading axis: the next-token
        module's block is one of them, behind the model's own."""
        return self.num_layers - self.conv_layers + self.mtp_layers

    @property
    def latent_width(self) -> int:
        """What a latent attention caches a token a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The pool's row: ``latent_width`` padded with zeros to the 128-lane
        grid the chip stores and the kernels' DMAs move (ops/mla_attention.py)."""
        return -(-self.latent_width // 128) * 128

    @property
    def state_bytes_per_slot(self) -> int:
        """A slot's share of the state store (float32, ops/ssm.py)."""
        tails = (self.conv_kernel - 1) * self.conv_channels
        matrix = self.ssm_inner * self.ssm_state * self.mamba2 + self.kda_inner * self.kda_head_dim * self.kda
        return self.conv_layers * (tails + matrix) * 4


Params = dict[str, Any]


PREFIX = "dense_"  # names of the leading dense layers' leaves in params["layers"]


def prefix_leaves(layers: Params) -> Params:
    """The leading dense layers' stack under the names a dense layer's leaves
    have (``attn_norm``, ``wq``, ..., ``mlp_norm``, ``wg``, ``wu``, ``wd``)."""
    return {n[len(PREFIX):]: w for n, w in layers.items() if n.startswith(PREFIX)}


def attention_leaves(cfg: MixtralConfig, count: int, dtype, matrix) -> Params:
    """One stack's attention leaves and the norms around both sublayers, less
    the FFN's own: ``matrix(name, shape)`` builds a (count, in, out) matrix
    at ``seeded_std(cfg, name)`` (quantised or not: both builds call this)."""
    H, Hq, Hkv, D = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ones = lambda width: jnp.ones((count, width), dtype)
    if cfg.kv_lora_rank:
        # W_DQ, W_UQ (heads x [nope, rope]), W_DKV ([latent, rotary key]), W_UKV
        # (heads x [k_nope, v]), W_O, and the two bottlenecks' norms.
        queries = ({"wdq": matrix("wdq", (count, H, cfg.q_lora_rank)), "q_a_norm": ones(cfg.q_lora_rank),
                    "wuq": matrix("wuq", (count, cfg.q_lora_rank, Hq * D))} if cfg.q_lora_rank
                   else {"wq": matrix("wq", (count, H, Hq * D))})   # (ISSUE 54: no bottleneck)
        # (ISSUE 56) under ``sandwich_norm`` both sublayers' outputs are normed: gains
        # that are NOT 1 (``seeded_std``), so that a norm dropped is another model.
        post = ({"post_attn_norm": jnp.full((count, H), seeded_std(cfg, "post_attn_norm"), dtype),
                 "post_ffn_norm": jnp.full((count, H), seeded_std(cfg, "post_ffn_norm"), dtype)}
                if cfg.sandwich_norm else {})
        return {"attn_norm": ones(H), **queries,
                "wdkv": matrix("wdkv", (count, H, cfg.latent_width)), "kv_a_norm": ones(cfg.kv_lora_rank),
                "wukv": matrix("wukv", (count, cfg.kv_lora_rank, Hq * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
                "wo": matrix("wo", (count, Hq * cfg.v_head_dim, H)), **post}
    out: Params = {
        "attn_norm": ones(H),
        "wq": matrix("wq", (count, H, Hq * D)), "wk": matrix("wk", (count, H, Hkv * D)),
        "wv": matrix("wv", (count, H, Hkv * D)), "wo": matrix("wo", (count, Hq * D, H)),
    }
    if cfg.qk_norm:
        gain = jnp.full((count, D), seeded_qk_gain(cfg), dtype)
        out.update(q_norm=gain, k_norm=gain)
    if cfg.attn_gate:
        out["attn_gate"] = matrix("attn_gate", (count, H, Hq * D))
    if cfg.sandwich_norm:
        out.update(post_attn_norm=ones(H), post_ffn_norm=ones(H))
    return out


def layer_leaves(cfg: MixtralConfig, count: int, dtype, matrix, plain=None) -> Params:
    """What a stack of ``count`` layers holds of the token mixer: its
    ``attention_leaves``; in a model with a per-layer kind the operator's input
    norm alone, the operators being stacks of their own (``operator_leaves``).
    With hyper-connections also both blocks' maps (``plain(name, shape)``
    builds a float32 leaf that is never quantised)."""
    if cfg.layer_conv is not None:
        return {"attn_norm": jnp.ones((count, cfg.hidden_size), dtype)}
    out = attention_leaves(cfg, count, dtype, matrix)
    if cfg.hc_mult:
        n = cfg.hc_mult
        for block in ("attn", "ffn"):
            out[f"hc_{block}_phi"] = plain("hc_phi", (count, n * cfg.hidden_size, 2 * n + n * n))
            out[f"hc_{block}_alpha"] = jnp.ones((count, 3), jnp.float32)
            out[f"hc_{block}_bias"] = plain("hc_bias", (count, 2 * n + n * n))
    return out


CONV_LEAVES = ("conv_in", "conv_w", "conv_out")
MAMBA_LEAVES = ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm", "out_proj")
KDA_LEAVES = ("kda_qkv", "conv_w", "kda_fa", "kda_fb", "dt_bias", "A_log", "kda_b", "kda_ga", "kda_gb", "kda_gbias",
              "kda_norm", "kda_out")
ATTENTION_STACK = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
LATENT_STACK = ("wdq", "q_a_norm", "wuq", "wdkv", "kv_a_norm", "wukv", "wo")


def state_leaves(cfg: "MixtralConfig") -> tuple:
    """The names of the state layers' stack."""
    return KDA_LEAVES if cfg.kda else MAMBA_LEAVES if cfg.mamba2 else CONV_LEAVES


def attention_stack(cfg: "MixtralConfig") -> tuple:
    """The names of the attention operators' stack in a model with a per-layer kind."""
    if cfg.kv_lora_rank:
        return LATENT_STACK if cfg.q_lora_rank else ("wq",) + LATENT_STACK[3:]
    return ATTENTION_STACK if cfg.qk_norm else ATTENTION_STACK[:4]


def operator_leaves(cfg: MixtralConfig, dtype, matrix, plain) -> Params:
    """The two operators' stacks of a model with a per-layer kind (none for
    any other): the attention operators' (``ATTENTION_STACK``, leading axis
    cache_layers) and the gated short convolutions' (``CONV_LEAVES``, leading
    axis conv_layers: W_in (H, 3H) whose thirds are B, C, u in that order, the
    taps (K, H) oldest first, W_out (H, H)). ``matrix`` as ``attention_leaves``
    takes it; ``plain(name, shape)`` builds a leaf that is never quantised."""
    if cfg.layer_conv is None:
        return {}
    H, n = cfg.hidden_size, cfg.conv_layers
    attention = attention_leaves(cfg, cfg.cache_layers, dtype, matrix)
    del attention["attn_norm"]   # the layers' own stacks hold every operator's input norm
    if cfg.kda:
        # W_qkv (H, [q P, k P, v P]), the taps (K, 3 P) oldest first, no bias; the decay's
        # and the gate's low-rank pairs (H, r), (r, P); beta's projection (H, heads),
        # never quantised; dt_bias (P,) and A_log (heads,), float32, at
        # ``kda_heads_init``; the gate's bias; the output norm a head; W_o (P, H).
        P, Hh, r = cfg.kda_inner, cfg.kda_heads, cfg.kda_rank or cfg.kda_head_dim
        return {**attention, "kda_qkv": matrix("kda_qkv", (n, H, 3 * P)), "conv_w": plain("conv_w", (n, cfg.conv_kernel, 3 * P)),
                "kda_fa": matrix("kda_fa", (n, H, r)), "kda_fb": matrix("kda_fb", (n, r, P)),
                **kda_heads_init(n, Hh, cfg.kda_head_dim), "kda_b": plain("kda_b", (n, H, Hh)),
                "kda_ga": matrix("kda_ga", (n, H, r)), "kda_gb": matrix("kda_gb", (n, r, P)),
                "kda_gbias": jnp.zeros((n, P), jnp.float32), "kda_norm": jnp.ones((n, cfg.kda_head_dim), dtype),
                "kda_out": matrix("kda_out", (n, P, H))}
    if cfg.mamba2:
        # W_in (H, [z Di, x Di, B N, C N, dt heads]), the taps (K, Di + 2 N) oldest
        # first and their bias, W_out (Di, H); dt_bias, A_log and D a head, float32,
        # at Mamba-2's published initialisation (``mamba_heads_init``).
        Di, C, Hh = cfg.ssm_inner, cfg.conv_channels, cfg.ssm_heads
        taps = plain("conv_w", (n, cfg.conv_kernel, C))
        gain = jnp.concatenate([jnp.ones((Di,)), jnp.full((C - Di,), seeded_std(cfg, "conv_bc_gain"))])
        return {**attention, "in_proj": matrix("in_proj", (n, H, 2 * Di + 2 * cfg.ssm_state + Hh)),
                "conv_w": (taps * gain).astype(taps.dtype), "conv_b": plain("conv_b", (n, C)),
                **mamba_heads_init(n, Hh), "ssm_norm": jnp.ones((n, Di), dtype),
                "out_proj": matrix("out_proj", (n, Di, H))}
    return {**attention, "conv_in": matrix("conv_in", (n, H, 3 * H)),
            "conv_w": plain("conv_w", (n, cfg.conv_kernel, H)), "conv_out": matrix("conv_out", (n, H, H))}


def mamba_heads_init(layers: int, heads: int) -> Params:
    """``A_log``, ``dt_bias`` and ``D`` of ``layers`` Mamba-2 mixers as the
    published initialisation spreads them (mamba_ssm's Mamba2: A uniform in
    [1, 16], Delta log-uniform in [0.001, 0.1] with dt_bias its inverse
    softplus, D = 1), laid out evenly over a layer's heads in place of drawn,
    the A's and the Delta's in orders coprime to each other, so that every
    build holds the same fast and slow heads and the slowest (Delta A = 0.001
    a token) is among them. Float32, (layers, heads) each."""
    i = jnp.arange(heads, dtype=jnp.float32)
    u = (i + 0.5) / heads
    v = ((i * 37 + heads // 2) % heads + 0.5) / heads   # 37 is coprime to every count of heads used (a power of two)
    dt = jnp.exp(jnp.log(0.001) + v * (jnp.log(0.1) - jnp.log(0.001)))
    lay = lambda a: jnp.broadcast_to(a[None, :], (layers, heads)).astype(jnp.float32)
    return {"A_log": lay(jnp.log(1.0 + 15.0 * u)), "dt_bias": lay(dt + jnp.log(-jnp.expm1(-dt))),
            "D": jnp.ones((layers, heads), jnp.float32)}


def kda_heads_init(layers: int, heads: int, head_dim: int) -> Params:
    """``A_log`` (layers, heads) and ``dt_bias`` (layers, heads x head_dim) of
    ``layers`` Kimi Delta Attention layers, float32, in the form of the family's
    modelling code (A uniform in [1, 16] a head; the decay's step log-uniform a
    channel with dt_bias its inverse softplus), laid out evenly in place of
    drawn (``mamba_heads_init``'s reasons). The steps spread over [0.0001, 0.1],
    a decade below the modelling code's [0.001, 0.1]: a channel's memory 1 / (A
    dt) then runs from a token to 10,000, the deployment's context, and a sixth
    of the channels remember more than a chunk, as a trained long-context
    model's must. At [0.001, 0.1] one channel in sixty did, and a state lost
    at a chunk boundary 320-500 tokens in front of the compared tokens read
    CORRECT on the chip (0.18 / 0.055 nats against a sound 0.17 / 0.033: my
    chip run, PR 54)."""
    u = (jnp.arange(heads, dtype=jnp.float32) + 0.5) / heads
    c = jnp.arange(heads * head_dim, dtype=jnp.float32)
    v = ((c * 37 + head_dim // 2) % head_dim + 0.5) / head_dim   # 37 is coprime to every head size used (a power of two)
    dt = jnp.exp(jnp.log(0.0001) + v * (jnp.log(0.1) - jnp.log(0.0001)))
    lay = lambda a: jnp.broadcast_to(a[None, :], (layers,) + a.shape).astype(jnp.float32)
    return {"A_log": lay(jnp.log(1.0 + 15.0 * u)), "dt_bias": lay(dt + jnp.log(-jnp.expm1(-dt)))}


def _init_layers(rng: jax.Array, cfg: MixtralConfig, L: int, dtype, lead: bool = True) -> tuple[Params, dict]:
    """A stack of ``L`` expert layers drawn from ``rng`` (with ``lead``, the
    leading dense layers' stack beside it), and the keys the tree's other
    leaves draw from: what ``init_params`` always drew for the model's own
    layers, and with another key the next-token module's one block (ISSUE 56)."""
    H, I, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    Eh = cfg.held_experts   # the stacks hold a chip's share; the router is E wide
    # One key a leaf the older mixtures have, as they always drew them; the
    # leaves ISSUE 41 added draw from a stream of their own.
    keys = dict(zip(("embed", "wq", "wk", "wv", "wo", "router", "wg", "wu", "wd", "lm_head"),
                    jax.random.split(rng, 10)))
    more = iter(jax.random.split(jax.random.fold_in(rng, 41), 24))

    def norm(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def matrix(name, shape):
        return norm(keys[name] if name in keys else next(more), shape, seeded_std(cfg, name))

    def lead_matrix(name, shape):
        return norm(next(more), shape, seeded_std(cfg, name))

    hc_keys = iter(jax.random.split(jax.random.fold_in(rng, 47), 8))

    def plain(name, shape):  # the hyper-connections' float32 leaves, a stream of their own
        return jax.random.normal(next(hc_keys), shape, jnp.float32) * seeded_std(cfg, name)

    layers: Params = {
        **layer_leaves(cfg, L, dtype, matrix, plain),
        **operator_leaves(cfg, dtype, matrix, lead_matrix),
        "moe_norm": jnp.ones((L, H), dtype),
        "router": matrix("router", (L, H, E)),
        # Expert FFNs: leading E axis → ep sharding.
        "wg": matrix("wg", (L, Eh, H, I)),
        "wu": matrix("wu", (L, Eh, H, I)),
        "wd": matrix("wd", (L, Eh, I, H)),
    }
    if cfg.indexer_topk:
        Hi, Di = cfg.indexer_heads, cfg.indexer_head_dim
        ikeys = iter(jax.random.split(jax.random.fold_in(rng, 36), 3))
        imatrix = lambda name, shape: norm(next(ikeys), shape, seeded_std(cfg, name))
        layers.update(wq_idx=imatrix("wq_idx", (L, H, Hi * Di)), wk_idx=imatrix("wk_idx", (L, H, Di)),
                      ww_idx=imatrix("ww_idx", (L, H, Hi)),
                      k_idx_norm=jnp.ones((L, Di), dtype), k_idx_bias=jnp.zeros((L, Di), dtype))
    if cfg.router_score == "sigmoid":
        layers["router_bias"] = matrix("router_bias", (L, E))
    if cfg.shared_experts:
        Is = cfg.shared_experts * I
        layers.update(shared_wg=matrix("shared_wg", (L, H, Is)), shared_wu=matrix("shared_wu", (L, H, Is)),
                      shared_wd=matrix("shared_wd", (L, Is, H)))
    if cfg.num_dense_layers and lead:
        Lp, Id = cfg.num_dense_layers, cfg.dense_intermediate_size
        first = {**layer_leaves(cfg, Lp, dtype, lead_matrix, plain), "mlp_norm": jnp.ones((Lp, H), dtype),
                 "wg": lead_matrix("dense_wg", (Lp, H, Id)), "wu": lead_matrix("dense_wu", (Lp, H, Id)),
                 "wd": lead_matrix("dense_wd", (Lp, Id, H))}   # (sized by their own names: seeded_std)
        layers.update({PREFIX + n: w for n, w in first.items()})
    return layers, {"embed": lambda shape: matrix("embed", shape), "lm_head": lambda shape: matrix("lm_head", shape),
                    "more": lead_matrix}


MTP_KEY = 56  # the next-token module's leaves draw from fold_in(rng, MTP_KEY): the model's own draw as ever


def init_params(rng: jax.Array, cfg: MixtralConfig, dtype=jnp.bfloat16) -> Params:
    H, V = cfg.hidden_size, cfg.vocab_size
    layers, draw = _init_layers(rng, cfg, cfg.expert_layers, dtype)
    params: Params = {"embed": draw["embed"]((V, H)), "layers": layers, "final_norm": jnp.ones((H,), dtype)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = draw["lm_head"]((H, V))
    if cfg.mtp_layers:
        # One block of the expert layers' kind, seeded by their rule, the two norms
        # in front of W_eh ([embedding half; stream half] -> hidden) and its own
        # final norm; embedding and head are the model's.
        block, draw = _init_layers(jax.random.fold_in(rng, MTP_KEY), cfg, cfg.mtp_layers, dtype, lead=False)
        params["mtp"] = {"enorm": jnp.ones((H,), dtype), "hnorm": jnp.ones((H,), dtype),
                         "eh_proj": draw["more"]("eh_proj", (2 * H, H)), "layers": block,
                         "final_norm": jnp.ones((H,), dtype)}
    return params


def init_cache(cfg: MixtralConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    shape = (cfg.cache_layers, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), **init_state(cfg, batch)}


def init_state(cfg: MixtralConfig, slots: int) -> Params:
    """The state store of a model with convolution layers, ``conv`` (conv
    layers, slots, K - 1, H) float32: a slot's last K - 1 values of B * u in
    each, oldest first (models/phi4flash.py's name and discipline: a segment
    that starts at position 0 starts from zero whatever the slot held). None
    for any other model."""
    if cfg.layer_conv is None:
        return {}
    state = {"conv": jnp.zeros((cfg.conv_layers, slots, cfg.conv_kernel - 1, cfg.conv_channels), jnp.float32)}
    if cfg.mamba2:   # a head's matrix state (ops/ssd.py)
        state["ssm"] = jnp.zeros((cfg.conv_layers, slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
    if cfg.kda:   # a head's matrix state, key x value (ops/kda.py)
        state["kda"] = jnp.zeros((cfg.conv_layers, slots, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32)
    return state


EXPERT_LEAVES = ("wg", "wu", "wd")
SHARED_LEAVES = ("shared_wg", "shared_wu", "shared_wd")


def _moe_block(flat: jnp.ndarray, routed: jnp.ndarray, router, experts: Params, li,
               cfg: MixtralConfig, valid=None, mesh=None, route=router_topk) -> tuple[jnp.ndarray, jnp.ndarray]:
    """flat: (N, H) the experts' normed input; routed: (N, H) the state
    the router reads; experts: the stacked expert leaves, read at index
    ``li`` of their stack; mesh: the one they are sharded over (param_specs),
    or None; route: who is chosen and what each weighs (ops/moe.py).
    Returns (output (N, H), rows each expert multiplied (E,)). The router
    is never quantised and its logits are float32."""
    with jax.named_scope("moe.router"):
        logits = jnp.dot(routed, router.astype(routed.dtype), preferred_element_type=jnp.float32)
    if cfg.moe_impl == "dense":
        lw = {n: jax.tree.map(lambda a: a[li], experts[n]) for n in EXPERT_LEAVES}

        def expert_fn(inp):  # (E, N, H)
            g = qeinsum("enh,ehi->eni", inp, lw["wg"])
            u = qeinsum("enh,ehi->eni", inp, lw["wu"])
            act = (ACTIVATIONS[cfg.hidden_act](g) * u).astype(inp.dtype)
            return qeinsum("eni,eih->enh", act, lw["wd"], out_dtype=inp.dtype)

        return (moe_dense(flat, logits, cfg.experts_per_token, expert_fn, route=route, first=cfg.experts_first,
                          held=cfg.held_experts),
                jnp.zeros((cfg.num_experts,), jnp.int32))
    return moe_dropless(flat, logits, cfg.experts_per_token, experts["wg"], experts["wu"],
                        experts["wd"], li, act=cfg.hidden_act, valid=valid, mesh=mesh, route=route,
                        first=cfg.experts_first)


@partial(jax.jit, static_argnames=("cfg", "mode", "last_only", "mesh"))
def forward(
    params: Params,
    cfg: MixtralConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    lengths: jnp.ndarray,
    cache: Params | None = None,
    mode: str = "prefill",
    last_only: bool = False,
    slot_ids: jnp.ndarray | None = None,
    mesh=None,
) -> tuple[jnp.ndarray, Params | None]:
    """Same contract as models/llama.forward (contiguous cache, masked
    einsum attention; ``--attention paged`` serves through forward_paged
    and forward_ragged). ``mesh``: the one the experts are sharded over."""
    if cfg.indexer_topk or cfg.kv_lora_rank:
        raise NotImplementedError(
            "a model with a sparse-attention indexer or a latent attention is served over the paged cache "
            "(--attention paged: the mixed step and the decode chunk); the dense cache has no path for it")
    B, T = tokens.shape
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    L = cfg.num_layers
    x = _embed(params, cfg, tokens)
    inv_freq = rope_inv_freq(cfg.hd, cfg.rope_theta, cfg.rope_scaling_dict)
    cos, sin = rope_cos_sin(positions, inv_freq)

    decode = mode == "decode"
    if decode:
        assert cache is not None
        S = cache["k"].shape[2]
        mask = decode_mask(S, lengths)
        in_window = jnp.arange(S)[None, None, :] > lengths[:, None, None] - 1 - (cfg.sliding_window or 0)
        scatter_pos = positions
        tok_valid = None
    else:
        mask = causal_prefill_mask(positions, lengths)
        in_window = positions[:, None, :] > positions[:, :, None] - (cfg.sliding_window or 0)
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        tok_valid = valid.reshape(B * T)
        if cache is not None:
            S = cache["k"].shape[2]
            scatter_pos = jnp.where(valid, positions, S)
        else:
            scatter_pos = None
    # One flag a layer, as the shared skeletons read them (llama._rope_qk,
    # _attend_by_window); here both kinds of layer are a mask apart.
    window_on = jnp.asarray(cfg.layer_window or (1,) * L, bool) & bool(cfg.sliding_window)
    seam = _seam(params, cfg, mesh)

    def attention(h, ap, li, kc, vc):
        """The attention operator: ``ap`` its leaves, kc / vc its cache rows
        (or None). Returns (the residual contribution, the new rows)."""
        q = qmatmul(h, ap["wq"]).reshape(B, T, Hq, D)
        k = qmatmul(h, ap["wk"]).reshape(B, T, Hkv, D)
        v = qmatmul(h, ap["wv"]).reshape(B, T, Hkv, D)
        q, k = _rope_qk(q, k, cos, sin, cfg, li, ap)
        if cfg.attention_multiplier is not None:   # gqa_attend scales by head_dim ** -0.5
            q = q * jnp.asarray(cfg.attention_multiplier * D ** 0.5, q.dtype)
        nk = nv = None
        if kc is not None:
            rows = (jnp.arange(B) if slot_ids is None else slot_ids)[:, None]
            nk = kc.at[rows, scatter_pos].set(k.astype(kc.dtype), mode="drop")
            nv = vc.at[rows, scatter_pos].set(v.astype(vc.dtype), mode="drop")
        layer_mask = mask & (in_window | ~window_on[li])
        if decode:
            attn = gqa_attend(q, nk.astype(q.dtype), nv.astype(q.dtype), layer_mask)
        else:
            attn = gqa_attend(q, k, v, layer_mask)
        return _attn_out(attn.reshape(B, T, Hq * D), h, ap, cfg), nk, nv

    def layer(x, lp, li, kc, vc, ffn):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        out, nk, nv = attention(h, lp, li, kc, vc)
        x = x + _scaled(out, cfg)
        y = ffn(x, h, lp, li, tok_valid)
        return x + _scaled(_ffn_out(y[0] if isinstance(y, tuple) else y, lp, cfg), cfg), nk, nv

    if cfg.layer_conv is not None:
        x, new_cache = _forward_by_kind(params, cfg, x, cache, attention, decode, lengths, slot_ids, tok_valid, seam)
        return _dense_head(params, cfg, x, lengths, mode, last_only), new_cache

    # The leading dense layers, where the model has them, then the expert
    # layers: one layer index over both groups.
    new_k, new_v = [], []
    for leaves, ffn, first, count in _layer_groups(params, seam["ffn"], seam["unscanned"], seam.get("prefix"), L):
        index = jnp.arange(first, first + count)
        if cache is not None:
            def body(x, per_layer, ffn=ffn):
                lp, li, kc, vc = per_layer
                x, nk, nv = layer(x, lp, li, kc, vc, ffn)
                return x, (nk, nv)

            x, (nk, nv) = jax.lax.scan(body, x, (leaves, index, cache["k"][first:first + count],
                                                 cache["v"][first:first + count]))
            new_k.append(nk)
            new_v.append(nv)
        else:
            def body(x, per_layer, ffn=ffn):
                lp, li = per_layer
                return layer(x, lp, li, None, None, ffn)[0], None

            x, _ = jax.lax.scan(body, x, (leaves, index))
    new_cache = None if cache is None else {"k": jnp.concatenate(new_k), "v": jnp.concatenate(new_v)}
    return _dense_head(params, cfg, x, lengths, mode, last_only), new_cache


def _dense_head(params: Params, cfg: MixtralConfig, x, lengths, mode: str, last_only: bool):
    """``forward``'s final norm and head: logits (B, T, V), or (B, V) of each
    row's last token."""
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if last_only:
        idx = jnp.maximum(lengths - 1, 0) if mode == "prefill" else jnp.zeros_like(lengths)
        x = x[jnp.arange(x.shape[0]), idx]
    return _head(params, cfg, x)


def _forward_by_kind(params, cfg: MixtralConfig, x, cache, attention, decode, lengths, slot_ids, tok_valid, seam):
    """``forward``'s layer loop for a model with a per-layer kind, over the
    dense slot cache (the tests' oracle and the engine the benchmark's
    reference child builds; serving goes through the paged skeletons). The
    layers are walked one by one, unrolled: each reads its operator's leaves
    and its cache rows at its ordinal among its kind. A prefill starts every
    row's state from zero and leaves the row's last (its last K - 1 inputs;
    a Mamba-2 layer's state after the row's last token, by the token
    recurrence: this path is the definition, not the serving one)."""
    B, T, H = x.shape
    layers, K = params["layers"], cfg.conv_kernel
    rows = jnp.arange(B) if slot_ids is None else slot_ids
    new = dict(init_state(cfg, B) if cache is None else cache)
    every = jnp.ones((B,), bool)

    def conv(z, tails, w, b=None, act=None):   # z (B*T, C); tails (slots, K - 1, C): the layer's whole entry
        if decode:
            c, mine = conv_decode(z, tails[rows], w, b, every, act=act)
            return c, tails.at[rows].set(mine)
        z = z.astype(jnp.float32).reshape(B, T, -1)
        padded = jnp.concatenate([jnp.zeros((B, K - 1, z.shape[-1]), z.dtype), z], axis=1)
        c = sum(w[j] * padded[:, j:j + T] for j in range(K))
        c = c if b is None else b + c
        last = lengths[:, None] + jnp.arange(K - 1)[None, :]              # the row's last K - 1, in ``padded``
        mine = jnp.take_along_axis(padded, last[:, :, None], axis=1)
        return (c if act is None else act(c)).reshape(B * T, -1), tails.at[rows].set(mine)

    def scan(ssm, ordinal, xs, delta, Bm, Cm, A, D, mm):
        if decode:
            y, mine = state_update(ssm[ordinal][rows], delta, xs, Bm, Cm, A, D, every)
        else:   # a padded token is an identity step: Delta = 0
            delta = jnp.where((jnp.arange(T)[None, :] < lengths[:, None])[..., None], delta.reshape(B, T, -1), 0.0)
            by_row = lambda a: a.reshape((B, T) + a.shape[1:])
            y, mine = jax.vmap(lambda xr, dr, br, cr: recurrence(jnp.zeros(ssm.shape[2:], jnp.float32), xr, dr, br, cr, A))(
                by_row(xs), delta, by_row(Bm), by_row(Cm))
            y = y.reshape(xs.shape) + D[None, :, None] * xs
        return y, ssm.at[ordinal, rows].set(mine)

    mix = (partial(_mamba2, cfg=cfg, conv=partial(conv, act=jax.nn.silu), scan=scan) if cfg.mamba2
           else partial(_shortconv, conv=conv))
    at = lambda stack, names, i: {n: jax.tree.map(lambda a: a[i], stack[n]) for n in names}
    body = _scan_layers(params, seam["unscanned"])   # the expert layers' norms, router and bias
    ordinal = [0, 0]
    for li, flag in enumerate(cfg.layer_conv):
        if li < cfg.num_dense_layers:
            lp, ffn = at(seam["prefix"]["layers"], seam["prefix"]["layers"], li), seam["prefix"]["ffn"]
        else:
            lp, ffn = at(body, body, li - cfg.num_dense_layers), seam["ffn"]
        is_state = int(flag > 0)
        o = ordinal[is_state]
        ordinal[is_state] += 1
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        if is_state:
            store = {n: new[n] for n in ("conv", "ssm") if n in new}
            out, store = mix(h.reshape(B * T, H), at(layers, state_leaves(cfg), o), store, o)
            out = out.reshape(B, T, H)
            new.update(store)
        else:
            kc, vc = (None, None) if cache is None else (new["k"][o], new["v"][o])
            out, nk, nv = attention(h, at(layers, attention_stack(cfg), o), li, kc, vc)
            if cache is not None:
                new["k"], new["v"] = new["k"].at[o].set(nk), new["v"].at[o].set(nv)
        x = x + _scaled(out, cfg)
        y = ffn(x, h, lp, li, tok_valid)
        x = x + _scaled(y[0] if isinstance(y, tuple) else y, cfg)
    return x, (None if cache is None else new)


def _entry(array, ordinal):
    """A state layer's entry of one of the store's arrays."""
    return jax.lax.dynamic_index_in_dim(array, ordinal, 0, keepdims=False)


def _with_entry(array, ordinal, entry):
    return jax.lax.dynamic_update_index_in_dim(array, entry, ordinal, 0)


def _shortconv(h: jnp.ndarray, sp: Params, store: Params, ordinal, conv) -> tuple[jnp.ndarray, Params]:
    """LFM2's gated short convolution on N tokens: h (N, H) the operator's
    normed input, ``sp`` its leaves, ``store["conv"][ordinal]`` what its slots
    remember. [B, C, u] = h W_in; z = B * u; c = the depthwise causal convolution of z
    (``conv(z, tails, taps) -> (c, new tails)``: ops/ssm.py's decode or ragged
    form, float32, no bias, no activation); the output (C * c) W_out."""
    with jax.named_scope("conv.short"):
        b, c, u = jnp.split(qmatmul(h, sp["conv_in"]).astype(jnp.float32), 3, axis=-1)
        y, tails = conv(b * u, _entry(store["conv"], ordinal), sp["conv_w"].astype(jnp.float32))
        return qmatmul((c * y).astype(h.dtype), sp["conv_out"]), {"conv": _with_entry(store["conv"], ordinal, tails)}


def _mamba2(h: jnp.ndarray, mp: Params, store: Params, ordinal, *, cfg: MixtralConfig, conv, scan):
    """Granite-4.0-H's Mamba-2 mixer on N tokens (ISSUE 50): h (N, H) the
    mixer's normed input, ``mp`` its leaves, the store's ``conv`` and ``ssm``
    entries at ``ordinal`` what its slots remember. [z, u, dt] = h W_in; [x, B,
    C] = silu(b + conv(u)) (``conv(u, tails, taps, bias) -> (c, new tails)``:
    ops/ssm.py's decode or ragged form); Delta = softplus(dt + dt_bias); the scan
    (``scan(ssm store, ordinal, x, Delta, B, C, A, D, the activations' type) ->
    (y, ssm store)``: ops/ssd.py's one-token update or its blocked form, the
    store whole so that a kernel updates it in place); the output (w * rmsnorm(y * silu(z))) W_out,
    the gate BEFORE the norm, over all of Di (one group). Everything between the
    two projections is float32."""
    Di, N, Hh = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
    with jax.named_scope("mamba"):  # the two projections and what stands between the state operations' own scopes
        proj = qmatmul(h, mp["in_proj"])
        z, u, dt = proj[:, :Di], proj[:, Di:2 * Di + 2 * N], proj[:, 2 * Di + 2 * N:]
        c, tails = conv(u, _entry(store["conv"], ordinal), mp["conv_w"].astype(jnp.float32),
                        mp["conv_b"].astype(jnp.float32))
        x, B, C = c[:, :Di].reshape(-1, Hh, cfg.ssm_head_dim), c[:, Di:Di + N], c[:, Di + N:]
        delta = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"])
        y, ssm = scan(store["ssm"], ordinal, x, delta, B, C, -jnp.exp(mp["A_log"]), mp["D"], h.dtype)
        with jax.named_scope("ssm.gate"):
            y = y.reshape(-1, Di) * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y, mp["ssm_norm"], cfg.rms_norm_eps)
        return (qmatmul(y.astype(h.dtype), mp["out_proj"]),
                {"conv": _with_entry(store["conv"], ordinal, tails), "ssm": ssm})


def _rows_by_length(q_starts, q_lens, kv_lens, T: int, block: int):
    """A mixed step's packed rows (row r = slot r) as a state layer with a chunk
    form splits them: a row of one token takes the batched one-token update at
    its packed position ``first``, a row of more the chunk form's visits
    (``ops/ssd.block_plan`` at ``block`` tokens); each from its slot's state,
    from zero where it starts at position 0 (``fresh``). Returns (the tokens'
    segments, fresh (S,), the one-token rows (S,), the tokens of rows of more
    (T,), the plan's meta, the visits it holds, each row's first packed token)."""
    seg, fresh = token_segments(q_starts, q_lens, T), (kv_lens - q_lens) == 0
    slot, _, live = seg
    one = q_lens == 1
    in_chunk = live & (q_lens[jnp.minimum(slot, q_lens.shape[0] - 1)] > 1)
    meta, blocks = block_plan(q_starts, q_lens, kv_lens, T, block)
    return seg, fresh, one, in_chunk, meta, blocks, jnp.minimum(q_starts, T - 1)


def _kda(h: jnp.ndarray, kp: Params, store: Params, ordinal, *, cfg: MixtralConfig, conv, scan):
    """Kimi Delta Attention on N tokens (ISSUE 54): h (N, H) the mixer's normed
    input, ``kp`` its leaves, the store's ``conv`` and ``kda`` entries at
    ``ordinal`` what its slots remember. [q, k, v] = silu(conv(h W_qkv))
    (``conv(u, tails, taps, None) -> (c, new tails)``: ops/ssm.py's decode or ragged
    form); a head's q and k l2-normalised, q times head_dim ** -0.5; the decay's
    log g = -exp(A_log) softplus((h W_fa) W_fb + dt_bias), a channel; beta =
    sigmoid(h w_b), a head; the delta rule (``scan(kda store, ordinal, q, k, v, g,
    beta, the activations' type) -> (o, kda store)``: ops/kda.py's one-token
    update or its chunk form, the store whole so that a kernel updates it in
    place); the output ((w * rmsnorm(o)) * sigmoid((h W_ga) W_gb + b)) W_o, the
    norm a head. Everything between the projections is float32."""
    P, Hh, D = cfg.kda_inner, cfg.kda_heads, cfg.kda_head_dim
    with jax.named_scope("mamba"):  # the projections and what stands between the state operations' own scopes
        c, tails = conv(qmatmul(h, kp["kda_qkv"]), _entry(store["conv"], ordinal), kp["conv_w"].astype(jnp.float32), None)
        q, k, v = (c[:, i * P:(i + 1) * P].reshape(-1, Hh, D) for i in range(3))
        unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        f = qmatmul(qmatmul(h, kp["kda_fa"]), kp["kda_fb"]).astype(jnp.float32)
        g = -jnp.exp(kp["A_log"])[None, :, None] * jax.nn.softplus(f + kp["dt_bias"]).reshape(-1, Hh, D)
        beta = jax.nn.sigmoid(jnp.dot(h, kp["kda_b"].astype(h.dtype), preferred_element_type=jnp.float32))
        o, states = scan(store["kda"], ordinal, unit(q) * D ** -0.5, unit(k), v, g, beta, h.dtype)
        with jax.named_scope("ssm.gate"):
            gate = qmatmul(qmatmul(h, kp["kda_ga"]), kp["kda_gb"]).astype(jnp.float32) + kp["kda_gbias"]
            o = rms_norm(o, kp["kda_norm"], cfg.rms_norm_eps) * jax.nn.sigmoid(gate.reshape(-1, Hh, D))
        return (qmatmul(o.reshape(-1, P).astype(h.dtype), kp["kda_out"]),
                {"conv": _with_entry(store["conv"], ordinal, tails), "kda": states})


def _kda_decode(rows) -> dict:
    """The mixer's two state operations for the decode chunk: one token a
    slot; ``rows`` (S,) the slots that carry a stream (the others keep theirs)."""
    def scan(states, ordinal, q, k, v, g, beta, mm):
        return kda_ops.update_store(states, ordinal, q, k, v, g, beta, rows)

    return {"conv": lambda u, tails, w, b: conv_decode(u, tails, w, b, rows), "scan": scan}


def _kda_ragged(cfg: MixtralConfig, q_starts, q_lens, kv_lens, T: int) -> tuple[dict, jnp.ndarray]:
    """The same for a mixed step's packed rows (``_mamba2_ragged``'s split: a row
    of one token takes the batched one-token update, a row of more the chunk
    form, ops/kda.py). Returns the operations and the step's counts, float32
    (3,): the blocks the chunk kernel walks over the state layers, the rows
    whose state the update reads and writes in each, and the plan's bound of
    those blocks."""
    seg, fresh, one, in_chunk, meta, blocks, first = _rows_by_length(q_starts, q_lens, kv_lens, T, cfg.kda_block)

    def scan(states, ordinal, q, k, v, g, beta, mm):
        o, states = kda_ops.kda_chunk(states, ordinal, q, k, v, g, beta, meta, Q=cfg.kda_block, mm=mm)
        o = jnp.where(in_chunk[:, None, None], o, 0.0)
        o1, states = kda_ops.update_store(states, ordinal, q[first], k[first], v[first], g[first], beta[first], one, fresh)
        return o.at[jnp.where(one, q_starts, T)].set(o1, mode="drop"), states

    ops = {"conv": lambda u, tails, w, b: conv_ragged(u, tails, w, b, seg, fresh, q_starts, q_lens), "scan": scan}
    return ops, jnp.stack([blocks * cfg.conv_layers, jnp.sum(one).astype(jnp.float32), float(meta.shape[0] * cfg.conv_layers)])


def _mamba2_decode(rows) -> dict:
    """The mixer's two state operations for the decode chunk: one token a
    slot; ``rows`` (S,) the slots that carry a stream (the others keep theirs)."""
    def scan(ssm, ordinal, x, delta, B, C, A, D, mm):
        return update_store(ssm, ordinal, delta, x, B, C, A, D, rows)

    return {"conv": lambda u, tails, w, b: conv_decode(u, tails, w, b, rows), "scan": scan}


def _mamba2_ragged(cfg: MixtralConfig, q_starts, q_lens, kv_lens, T: int) -> tuple[dict, jnp.ndarray]:
    """The same for a mixed step's packed rows (row r = slot r): a row of one
    token takes the batched one-token update at its packed position, a row of
    more the blocked scan (ops/ssd.py); each from its slot's state, from zero
    where it starts at position 0. Returns the operations and the step's
    counts, float32 (3,): the blocks the chunk kernel walks over the state
    layers, the rows whose state the update reads and writes in each, and the
    plan's bound of those blocks."""
    seg, fresh, one, in_chunk, meta, blocks, first = _rows_by_length(q_starts, q_lens, kv_lens, T, cfg.ssd_block)

    def scan(ssm, ordinal, x, delta, B, C, A, D, mm):
        y, ssm = ssd_chunk(ssm, ordinal, x, delta, B, C, A, meta, Q=cfg.ssd_block, mm=mm)
        y = jnp.where(in_chunk[:, None, None], y + D[None, :, None] * x, 0.0)
        y1, ssm = update_store(ssm, ordinal, delta[first], x[first], B[first], C[first], A, D, one, fresh)
        return y.at[jnp.where(one, q_starts, T)].set(y1, mode="drop"), ssm

    ops = {"conv": lambda u, tails, w, b: conv_ragged(u, tails, w, b, seg, fresh, q_starts, q_lens), "scan": scan}
    return ops, jnp.stack([blocks * cfg.conv_layers, jnp.sum(one).astype(jnp.float32), float(meta.shape[0] * cfg.conv_layers)])


def _moe_ffn(x: jnp.ndarray, attn_in: jnp.ndarray, lp: Params, li, valid, *,
             cfg: MixtralConfig, experts: Params, mesh=None):
    """The FFN seam of the shared skeletons (llama.forward_paged_impl):
    norm + mixture (+ the shared expert, added once); the auxiliary is the
    rows each expert multiplied. ``li`` is the layer's index in the model;
    the expert stacks begin behind the leading dense layers."""
    B, T, H = x.shape
    h = rms_norm(x, lp["moe_norm"], cfg.rms_norm_eps)
    routed = attn_in if cfg.router_input == "attn" else h
    route = router_topk
    if cfg.router_score == "sigmoid":
        route = partial(router_sigmoid, bias=lp["router_bias"], scale=cfg.route_scale, eps=cfg.route_eps)
    # (No subtraction where nothing leads: an older mixture traces as it did.)
    stack_index = li - cfg.num_dense_layers if cfg.num_dense_layers else li
    out, rows = _moe_block(h.reshape(B * T, H), routed.reshape(B * T, H), lp["router"],
                           experts, stack_index, cfg, valid, mesh, route)
    out = out.reshape(B, T, H)
    if cfg.shared_experts:
        with jax.named_scope("moe.shared"):
            act = ACTIVATIONS[cfg.hidden_act]
            sp = lp if "shared_wg" in lp else {n: jax.tree.map(lambda a: a[stack_index], experts[n])
                                               for n in SHARED_LEAVES}
            out = out + qmatmul(act(qmatmul(h, sp["shared_wg"])) * qmatmul(h, sp["shared_wu"]), sp["shared_wd"])
    return out, rows


def _indexer(h: jnp.ndarray, lp: Params, positions: jnp.ndarray, cfg: MixtralConfig):
    """The lightning indexer's side of N tokens: h (N, H) the attention's
    normed input -> queries (N, Hi, Di) and this token's key (N, Di), both
    rotated (rotate-half over the indexer's own Di, the model's theta; the
    key LayerNormed first), and the heads' weights (N, Hi) in float32 with
    both scales folded in."""
    Hi, Di = cfg.indexer_heads, cfg.indexer_head_dim
    cos, sin = rope_cos_sin(positions, rope_inv_freq(Di, cfg.rope_theta))
    qi = apply_rope(qmatmul(h, lp["wq_idx"]).reshape(-1, Hi, Di), cos, sin)
    kf = (h @ lp["wk_idx"]).astype(jnp.float32)
    kf = (kf - jnp.mean(kf, axis=-1, keepdims=True)) * jax.lax.rsqrt(jnp.var(kf, axis=-1, keepdims=True) + 1e-6)
    kf = kf * lp["k_idx_norm"].astype(jnp.float32) + lp["k_idx_bias"].astype(jnp.float32)
    ki = apply_rope(kf.astype(h.dtype)[:, None, :], cos, sin)[:, 0]
    wi = (h @ lp["ww_idx"]).astype(jnp.float32) * (Hi ** -0.5 * Di ** -0.5)
    return qi, ki, wi


DSA_CACHE = ("ki",)  # the indexer's keys, a page transposed: (L, P, indexer_head_dim, page_size)


def _index_and_write(h, lp, positions, pool, w_idx, page_size: int, cfg: MixtralConfig):
    """The indexer's side of a step's tokens, with their keys ``ki`` (N, Di)
    written into the pool (pages, Di, page_size) at the flat token indices
    ``w_idx`` (past the pool: dropped), a column of a page each. Returns
    (queries, weights, the pool)."""
    with jax.named_scope("dsa.index"):
        qi, ki, wi = _indexer(h, lp, positions, cfg)
        return qi, wi, pool.at[w_idx // page_size, :, w_idx % page_size].set(ki.astype(pool.dtype), mode="drop")


def _dsa_decode(h, q, lp, li, positions, pools, w_idx, table, lengths, *, page_size, cfg, valid=None):
    """The attention seam's decode side (llama.forward_paged_impl): write
    this token's indexer key, then score, select and read the chosen tokens
    (ops/sparse_attention.sparse_decode; a row that sees ``indexer_topk``
    tokens or fewer keeps them all: plain causal attention, by the same
    path). The auxiliary is (tokens the live rows' queries could see,
    tokens whose keys and values their attention read)."""
    del li
    qi, wi, cki = _index_and_write(h, lp, positions, pools["ki"], w_idx, page_size, cfg)
    if valid is not None:   # a row that carries no stream sees nothing: the walk passes over it
        lengths = jnp.where(valid, lengths, 0)
    attn = sparse_decode(q, qi, wi, pools["k"], pools["v"], cki, table, lengths, topk=cfg.indexer_topk,
                         num_kv_heads=cfg.num_kv_heads, page_size=page_size)
    seen = jnp.sum(lengths)
    read = jnp.sum(jnp.where(lengths > 0, one_query_tokens_read(
        cfg.indexer_topk, table.shape[1] * page_size, page_size, lengths), 0))
    return attn, {"ki": cki}, jnp.stack([seen, read]).astype(jnp.float32)


def _dsa_ragged(h, q, lp, li, positions, pools, w_idx, table, q_starts, q_lens, kv_lens, *,
                page_size, cfg):
    """The seam's mixed-step side (llama.forward_ragged_impl): the same over
    the packed rows; a prompt chunk's queries each keep a set of their own
    (ops/sparse_attention.sparse_ragged)."""
    del li
    qi, wi, cki = _index_and_write(h, lp, positions, pools["ki"], w_idx, page_size, cfg)
    sees = jnp.where(jnp.arange(q.shape[0]) < jnp.sum(q_lens), positions + 1, 0)   # a packed query's context
    attn, walked = sparse_ragged(q, qi, wi, positions, pools["k"], pools["v"], cki, table, q_starts,
                                 q_lens, kv_lens, topk=cfg.indexer_topk, num_kv_heads=cfg.num_kv_heads,
                                 page_size=page_size)
    return attn, {"ki": cki}, jnp.stack([jnp.sum(sees), jnp.sum(walked)]).astype(jnp.float32)


MLA_POOL = "kv"  # the latent attention's one pool: (L, P, page_size, latent_row)


def _latent_absorb(q_nope, w, cfg: MixtralConfig):
    """The heads' q_nope (N, heads, nope) carried into the latent's space by
    their blocks of W_UK, (N, heads, rank), beside W_UV (rank, heads, v) for
    ``_latent_out``. W_UKV ``w`` (rank, heads * (nope + v)) is multiplied AS IT
    IS STORED (ISSUE 59): a ``QTensor``'s int8 is converted inside each
    product's own fusion and no array of the matrix's size is written (the
    parent wrote it in the activations' dtype, then laid it out by head and
    scaled it, every block of every step). A column's scale lies on the
    CONTRACTED axis of W_UK's product, so the query carries it (float32, cast
    once); on W_UV's it lies on the result's, so W_UV goes on as a ``QTensor``
    of its own and ``qeinsum`` scales the float32 product, as for every other
    matrix. A plain leaf takes the same two einsums on the array as it is."""
    Hq, Dn, Dv, dtype = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, q_nope.dtype
    scale = w.scale.reshape(Hq, Dn + Dv) if hasattr(w, "scale") else None
    if scale is not None:
        q_nope, w = (q_nope.astype(jnp.float32) * scale[:, :Dn]).astype(dtype), w.q
    w = w.reshape(cfg.kv_lora_rank, Hq, Dn + Dv)
    q_lat = jnp.einsum("nhd,chd->nhc", q_nope, w[..., :Dn].astype(dtype), preferred_element_type=jnp.float32)
    return q_lat.astype(dtype), w[..., Dn:] if scale is None else QTensor(w[..., Dn:], scale[:, Dn:])


def _latent_project(h, lp: Params, positions, cfg: MixtralConfig):
    """The latent attention's projections of N tokens' normed input h (N, H):
    the ABSORBED queries (N, heads, latent_width), a head's q_nope carried
    into the latent's space by its block of W_UK beside its rotary part, and
    the tokens' pool rows (N, latent_row): [the normed latent, the rotary key,
    zeros]; returns also W_UV as ``_latent_absorb`` hands it on."""
    N = h.shape[0]
    Hq, Dn, Dr, rank = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    if cfg.layer_rope is None:
        cos, sin = rope_cos_sin(positions, rope_inv_freq(Dr, cfg.rope_theta, cfg.rope_scaling_dict))
        rotate = lambda a: apply_rope(a, cos, sin)
    else:   # no position encoding (ISSUE 54): the shared lanes are scored as they are projected
        rotate = lambda a: a
    with jax.named_scope("mla.project"):
        if cfg.q_lora_rank:
            c_q = rms_norm(qmatmul(h, lp["wdq"]), lp["q_a_norm"], cfg.rms_norm_eps)
            q = qmatmul(c_q, lp["wuq"]).reshape(N, Hq, Dn + Dr)
        else:
            q = qmatmul(h, lp["wq"]).reshape(N, Hq, Dn + Dr)
        q_rope = rotate(q[..., Dn:])
        down = qmatmul(h, lp["wdkv"])
        latent = rms_norm(down[:, :rank], lp["kv_a_norm"], cfg.rms_norm_eps)
        k_rope = rotate(down[:, None, rank:])[:, 0]
        row = jnp.concatenate([latent, k_rope, jnp.zeros((N, cfg.latent_row - cfg.latent_width), h.dtype)], axis=-1)
    with jax.named_scope("mla.absorb"):
        q_lat, w_uv = _latent_absorb(q[..., :Dn], lp["wukv"], cfg)
        q_abs = jnp.concatenate([q_lat, q_rope], axis=-1)
    return q_abs, row, w_uv


def _latent_out(attn, w_uv, dtype):
    """The heads' weighted latents (N, heads, rank) up through W_UV (rank, heads, v),
    a plain array or the stored int8 under its columns' scales (heads, v): (N, heads * v)."""
    with jax.named_scope("mla.absorb"):
        out = qeinsum("nhc,chd->nhd", attn, w_uv)
        return out.reshape(out.shape[0], -1).astype(dtype)


def mla_scale(cfg: MixtralConfig) -> float:
    """The scores' factor: head_dim ** -0.5 times YaRN's m squared."""
    return cfg.hd ** -0.5 * yarn_mscale(cfg.rope_scaling_dict) ** 2


def _latent_attention(h, lp, positions, pool, w_idx, page_size: int, cfg: MixtralConfig, read):
    """Both sides of the owning attention seam: project, write the tokens' rows
    into the flat pool, ``read(queries, pages)`` them in the absorbed form (each
    page once, as key and as value), and carry the weighted latents up."""
    q, row, w_uv = _latent_project(h, lp, positions, cfg)
    pool = pool.at[w_idx].set(row.astype(pool.dtype), mode="drop")
    attn = read(q, pool.reshape(-1, page_size, pool.shape[-1]), value_dim=cfg.kv_lora_rank, scale=mla_scale(cfg))
    return _latent_out(attn, w_uv, h.dtype), pool


def _mla_decode(h, lp, li, positions, pool, w_idx, table, lengths, *, page_size, cfg, valid=None):
    """The seam's decode side (llama.forward_paged_impl): one query a slot."""
    del li
    from inference_gateway_tpu.ops.mla_attention import mla_paged_attention

    if valid is not None:  # a row that carries no stream sees nothing: the walk passes over it
        lengths = jnp.where(valid, lengths, 0)
    return _latent_attention(h, lp, positions, pool, w_idx, page_size, cfg,
                             lambda q, pages, **kw: mla_paged_attention(q, pages, table, lengths, **kw))


def _mla_ragged(h, lp, li, positions, pool, w_idx, table, q_starts, q_lens, kv_lens, *, page_size, cfg, pair=None):
    """The seam's mixed-step side: the packed rows; a chunk's queries go
    absorbed through the same walk, a tile of them at a time (``pair``: a
    drafting model's rows of two queries, ops/mla_attention.py)."""
    del li
    from inference_gateway_tpu.ops.mla_attention import mla_ragged_attention

    return _latent_attention(h, lp, positions, pool, w_idx, page_size, cfg,
                             lambda q, pages, **kw: mla_ragged_attention(q, pages, table, q_starts, q_lens, kv_lens,
                                                                         pair=pair, **kw))


def _hyper_connections(cfg: MixtralConfig) -> dict:
    """The residual seam (llama.forward_paged_impl) for ``hc_mult`` streams a
    token: every stream starts as the embedding, a block reads H_pre X and is
    written back as H_res X + H_post^T y, the final norm reads their sum. The
    layer loop carries them stream-major, (hc_mult, B, T, hidden)."""
    from inference_gateway_tpu.ops import hyper_connections as hc

    n = cfg.hc_mult

    def read(X, lp, block):
        flat = X.reshape(n, -1, X.shape[-1])
        pre, post, res = hc.maps(flat, lp[f"hc_{block}_phi"], lp[f"hc_{block}_alpha"], lp[f"hc_{block}_bias"],
                                 iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, clamp=cfg.hc_clamp,
                                 norm_eps=cfg.rms_norm_eps)
        return hc.read(flat, pre).reshape(X.shape[1:]), (post, res)

    def write(X, y, maps):
        return hc.write(X.reshape(n, -1, X.shape[-1]), y.reshape(-1, y.shape[-1]), *maps).reshape(X.shape)

    return {"enter": lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
            "read": read, "write": write,
            "exit": lambda X: jnp.sum(X.astype(jnp.float32), axis=0).astype(X.dtype)}


def _seam(params: Params, cfg: MixtralConfig, mesh, valid=None, conv=None, pair=None) -> dict:
    """What the shared skeletons take for a mixture: the FFN seam, the
    leaves it reads whole by layer index (never sliced per layer), for a
    model with leading dense layers their group, for a model with an
    indexer the attention seam, and for a model with convolution layers the
    per-layer kind (``conv``: the step's form of the convolution, ``_shortconv``'s;
    for Mamba-2 layers the step's forms of both state operations, ``_mamba2``'s)."""
    experts = {n: params["layers"][n] for n in EXPERT_LEAVES}
    if cfg.scans_runs and cfg.shared_experts:   # read whole at the layer's index, as the experts are (``_layer_groups``' runs)
        experts.update({n: params["layers"][n] for n in SHARED_LEAVES})
    seam = {"ffn": partial(_moe_ffn, cfg=cfg, experts=experts, mesh=mesh), "unscanned": EXPERT_LEAVES}
    if cfg.layer_conv is not None:
        # (A Mamba-2 model's store is too large to stand under a ``lax.cond``: its
        # layers are scanned in runs of one kind, and the shared MLP's matrices,
        # which a run's scan would slice, are read at the layer's index instead.)
        names, operators = state_leaves(cfg), attention_stack(cfg)
        mix = (partial(_kda, cfg=cfg, **(conv or {})) if cfg.kda else
               partial(_mamba2, cfg=cfg, **(conv or {})) if cfg.mamba2 else partial(_shortconv, conv=conv))
        seam["kinds"] = {"state": tuple(int(f > 0) for f in cfg.layer_conv),
                         "cache": ("conv", "kda") if cfg.kda else ("conv", "ssm") if cfg.mamba2 else ("conv",),
                         "mix": mix, "runs": cfg.scans_runs,
                         "attention": {n: params["layers"][n] for n in operators},
                         "leaves": {n: params["layers"][n] for n in names}}
        seam["unscanned"] += operators + names + (SHARED_LEAVES if cfg.scans_runs and cfg.shared_experts else ())
    if cfg.num_dense_layers:
        # The leading dense layers: a scanned group of their own in front.
        lead = prefix_leaves(params["layers"])
        seam["prefix"] = {"layers": lead, "ffn": partial(_dense_ffn, cfg=cfg)}
        seam["unscanned"] += tuple(PREFIX + n for n in lead)
    if cfg.indexer_topk:
        seam["mixer"] = {"cache": DSA_CACHE,
                         "decode": partial(_dsa_decode, cfg=cfg, valid=valid),
                         "ragged": partial(_dsa_ragged, cfg=cfg)}
    if cfg.kv_lora_rank:
        seam["mixer"] = {"cache": (), "pool": MLA_POOL,
                         "decode": partial(_mla_decode, cfg=cfg, valid=valid),
                         "ragged": partial(_mla_ragged, cfg=cfg, pair=pair)}
    if cfg.hc_mult:
        seam["residual"] = _hyper_connections(cfg)
    return seam


def counts_window_pages(cfg: MixtralConfig) -> bool:
    """A mixture with windowed layers counts, in both step programs, the pages
    those layers read and hold (ISSUE 41; not one with an indexer: its
    attention reads what the indexer chooses). So does one whose pool holds
    its caching layers alone (ISSUE 43): no window there, so both counts are
    the pages its rows hold, over the caching layers."""
    return (windowed_layers(cfg) > 0 and not cfg.indexer_topk) or cfg.layer_conv is not None or bool(cfg.kv_lora_rank)


def counted_window_pages(cfg: MixtralConfig, page_size: int, first_len, last_len, max_queries: int = 1):
    """``llama.window_pages`` over this model's windowed layers (its caching
    layers, with a window no context reaches, where it has convolution layers)."""
    if cfg.kv_lora_rank:
        return latent_pages(cfg, page_size, first_len, last_len, max_queries)
    if cfg.layer_conv is not None:
        return window_pages(cfg.cache_layers, 1 << 30, page_size, first_len, last_len)
    return window_pages(windowed_layers(cfg), cfg.sliding_window, page_size, first_len, last_len)


def latent_pages(cfg: MixtralConfig, page_size: int, first_len, last_len, max_queries: int):
    """What a step's rows WALK of the latent pool and what their contexts hold,
    in pages over all layers (ISSUE 47; the engine's LATENT_STATS): a row whose
    queries (``max_queries`` at the most) see lengths first_len..last_len (0 = no row) is walked a tile of
    ``chunk_tile`` queries at a time, each tile from the row's first page to
    its own horizon (ops/mla_attention.py); a one-query row is one tile.
    Returns float32 (3,): latent_pages_read, context_pages, and of the first
    what the one-query rows walked (latent_row_pages_read: the rest is the
    chunks' tiles')."""
    from inference_gateway_tpu.ops.mla_attention import chunk_tile

    tile = chunk_tile(cfg.num_heads, page_size)
    live = last_len > 0
    queries = jnp.where(live, last_len - first_len + 1, 0)
    c = jnp.arange(-(-max_queries // tile), dtype=last_len.dtype)[None, :]  # the tiles a row can have
    ends = jnp.minimum(first_len[:, None] + (c + 1) * tile - 1, last_len[:, None])
    walked = jnp.where(c * tile < queries[:, None], (ends + page_size - 1) // page_size, 0)
    pages = jnp.where(live, (last_len + page_size - 1) // page_size, 0)
    return cfg.cache_layers * jnp.stack([jnp.sum(walked), jnp.sum(pages),
                                       jnp.sum(jnp.where(queries == 1, pages, 0))]).astype(jnp.float32)


def init_paged_cache(cfg: MixtralConfig, cache_cfg, dtype=jnp.bfloat16) -> Params:
    """The page pool of a model that keeps more than every layer's k and v
    (serving/kv_cache.init_paged_cache): with an indexer, its keys under the
    same page ids; with convolution layers, planes for the caching layers
    alone and the state store beside them (``init_state``)."""
    pages = cache_cfg.resolve_num_pages()
    if cfg.kv_lora_rank:  # one array, a latent row a token a layer: no k, no v
        return {MLA_POOL: jnp.zeros((cfg.cache_layers, pages, cache_cfg.page_size, cfg.latent_row), dtype),
                **init_state(cfg, cache_cfg.max_slots)}
    plane = (cfg.cache_layers, pages, cache_cfg.page_size)
    cache = {"k": jnp.zeros(plane + (cfg.num_kv_heads * cfg.hd,), dtype),
             "v": jnp.zeros(plane + (cfg.num_kv_heads * cfg.hd,), dtype), **init_state(cfg, cache_cfg.max_slots)}
    if cfg.indexer_topk:
        # A page of indexer keys lies transposed, the tokens in the lanes: 64 bf16
        # a token would be half a lane row, padded to a whole one on the device.
        cache["ki"] = jnp.zeros(plane[:2] + (cfg.indexer_head_dim, cache_cfg.page_size), dtype)
    return cache


def _page_size(cfg: MixtralConfig, cache: Params) -> int:
    return cache[MLA_POOL if cfg.kv_lora_rank else "k"].shape[2]


@partial(jax.jit, static_argnames=("cfg", "mode", "last_only", "mesh", "with_aux"))
def forward_paged(
    params: Params,
    cfg: MixtralConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    lengths: jnp.ndarray,
    cache: Params,
    write_idx: jnp.ndarray,
    page_table: jnp.ndarray,
    mode: str = "prefill",
    last_only: bool = True,
    mesh=None,
    valid: jnp.ndarray | None = None,
    with_aux: bool = False,
):
    """Paged-KV MoE serving: attention and paging are the shared
    skeleton (llama.forward_paged_impl); experts ride the seam. With
    ``with_aux`` a third result is the rows each expert multiplied,
    (expert layers, E) (with an indexer, or windowed layers: a pair, that
    and the seam's counts or ``counted_window_pages``); ``valid`` (B,)
    marks the decode rows that carry a stream."""
    conv = None
    if cfg.layer_conv is not None:
        with jax.named_scope("carry"):
            rows = jnp.ones(lengths.shape, bool) if valid is None else valid   # a row that carries no stream keeps its tail
        conv = _kda_decode(rows) if cfg.kda else _mamba2_decode(rows) if cfg.mamba2 else (
            lambda z, tails, w: conv_decode(z, tails, w, None, rows, act=None))
    out = forward_paged_impl(params, cfg, tokens, positions, lengths, cache,
                             write_idx, page_table, mode, last_only, mesh,
                             valid=valid, with_aux=with_aux, **_seam(params, cfg, mesh, valid, conv))
    if not (with_aux and counts_window_pages(cfg)):
        return out
    with jax.named_scope("carry"):
        live = lengths if valid is None else jnp.where(valid, lengths, 0)
        counts = counted_window_pages(cfg, _page_size(cfg, cache), live, live)
        if cfg.scans_runs:   # no block is walked; every live row's state is read and written, in every state layer
            counts = jnp.concatenate([counts, jnp.stack([0.0, jnp.sum(live > 0).astype(jnp.float32), 0.0])])
    return (*out[:2], (out[2], counts))


@partial(jax.jit, static_argnames=("cfg", "mesh", "with_aux", "hidden", "pair"))
def forward_ragged(
    params: Params,
    cfg: MixtralConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Params,
    write_idx: jnp.ndarray,
    page_table: jnp.ndarray,
    q_starts: jnp.ndarray,
    q_lens: jnp.ndarray,
    kv_lens: jnp.ndarray,
    mesh=None,
    with_aux: bool = False,
    hidden: bool = False,
    tok_valid: jnp.ndarray | None = None,
    pair: str | None = None,
):
    """The mixed step for a mixture (llama.forward_ragged's contract; the
    third result as ``forward_paged``'s). ``hidden``, ``tok_valid``:
    ``forward_ragged_impl``'s; ``pair``: ``mla_ragged_attention_tpu``'s (ISSUE 56)."""
    conv = None
    with jax.named_scope("carry"):  # the rows' segments and the blocked scan's plan: once a step, for every state layer
        if cfg.kda:
            conv, state_counts = _kda_ragged(cfg, q_starts, q_lens, kv_lens, tokens.shape[1])
        elif cfg.mamba2:
            conv, state_counts = _mamba2_ragged(cfg, q_starts, q_lens, kv_lens, tokens.shape[1])
        elif cfg.layer_conv is not None:
            # Each segment from its slot's tail; one that starts at position 0
            # from zero, whatever the slot held (ops/ssm.py).
            seg, fresh = token_segments(q_starts, q_lens, tokens.shape[1]), (kv_lens - q_lens) == 0
            conv = lambda z, tails, w: conv_ragged(z, tails, w, None, seg, fresh, q_starts, q_lens, act=None)
    out = forward_ragged_impl(params, cfg, tokens, positions, cache, write_idx,
                              page_table, q_starts, q_lens, kv_lens, mesh,
                              with_aux=with_aux, hidden=hidden, tok_valid=tok_valid,
                              **_seam(params, cfg, mesh, conv=conv, pair=pair))
    if not (with_aux and counts_window_pages(cfg)):
        return out
    with jax.named_scope("carry"):
        live = jnp.where(q_lens > 0, kv_lens, 0)
        counts = counted_window_pages(cfg, _page_size(cfg, cache), live - q_lens + 1, live, tokens.shape[1])
        if cfg.scans_runs:   # blocks the chunk kernel walked, over the state layers; rows the one-token update served
            counts = jnp.concatenate([counts, state_counts])
    return (*out[:2], (out[2], counts))


def head_logits(params: Params, cfg: MixtralConfig, x, final_norm):
    """Float32 logits of streams ``x`` (..., H) through ``final_norm`` and the
    model's head (the model's own final norm, or the next-token module's)."""
    with jax.named_scope("head"):
        return _head(params, cfg, rms_norm(x, final_norm, cfg.rms_norm_eps))


def mtp_ragged(params: Params, cfg: MixtralConfig, stream, next_tokens, positions, cache: Params, write_idx,
               page_table, q_starts, q_lens, kv_lens, tok_valid, mesh=None, pair: str | None = None):
    """The next-token module over a step's packed tokens (ISSUE 56): ``stream``
    (T, H) the last block's stream of each, ``next_tokens`` (T,) the token that
    FOLLOWS each; positions, write indices and row descriptors as the step's
    own, less the queries whose next token is not known (the caller shortens
    ``q_lens`` / ``kv_lens`` and drops their write indices). u = W_eh
    [norm_e(Emb(next)); norm_h(stream)], one block of the expert layers' kind
    over the module's plane of the pool (the last), causal over u. Returns (the
    block's stream (T, H), before the module's final norm; the cache; the rows
    each expert multiplied (E,))."""
    mp = params["mtp"]
    block = mp["layers"]
    pool = cache[MLA_POOL]
    L, P, page_size, width = pool.shape
    flat = P * page_size
    plane = L - 1
    with jax.named_scope("embed"):
        e = rms_norm(_embed(params, cfg, next_tokens), mp["enorm"], cfg.rms_norm_eps)
        s = rms_norm(stream, mp["hnorm"], cfg.rms_norm_eps)
        x = qmatmul(jnp.concatenate([e, s.astype(e.dtype)], axis=-1), mp["eh_proj"])
    lp = {n: jax.tree.map(lambda a: a[0], w) for n, w in block.items() if n not in EXPERT_LEAVES}
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        w_idx = jnp.where(write_idx >= flat, L * flat, write_idx + plane * flat)
        attn, pool = _mla_ragged(h, lp, plane, positions, pool.reshape(L * flat, width), w_idx, page_table + plane * P,
                                 q_starts, q_lens, kv_lens, page_size=page_size, cfg=cfg, pair=pair)
        x = x + _attn_out(attn, h, lp, cfg)
    with jax.named_scope("mlp"):
        experts = {n: block[n] for n in EXPERT_LEAVES}
        y, rows = _moe_ffn(x[None], h[None], lp, cfg.num_dense_layers, tok_valid, cfg=cfg, experts=experts, mesh=mesh)
        x = x + _ffn_out(y[0], lp, cfg)
    return x, {**cache, MLA_POOL: pool.reshape(L, P, page_size, width)}, rows


def param_specs(cfg: MixtralConfig) -> dict:
    """PartitionSpecs: experts on ep, tp inside each expert FFN."""
    from jax.sharding import PartitionSpec as P

    specs = {
        "embed": P("tp", None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "moe_norm": P(None, None),
            "router": P(None, None, None),
            "wg": P(None, "ep", None, "tp"),
            "wu": P(None, "ep", None, "tp"),
            "wd": P(None, "ep", "tp", None),
        },
        "final_norm": P(None),
    }
    if cfg.qk_norm:
        specs["layers"].update(q_norm=P(None, None), k_norm=P(None, None))
    if cfg.indexer_topk:  # replicated: no mesh carries the indexer or its cache yet
        specs["layers"].update(wq_idx=P(None, None, None), wk_idx=P(None, None, None),
                               ww_idx=P(None, None, None), k_idx_norm=P(None, None),
                               k_idx_bias=P(None, None))
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def _smallthinker(num_layers: int, **sizes) -> MixtralConfig:
    """SmallThinker's block: the router reads the attention's normed
    input, ReGLU experts, and of every four layers the first is global
    with no position encoding, the other three windowed with rotary
    (``rope_layout`` and ``sliding_window_layout`` of its config.json)."""
    layout = tuple(int(i % 4 != 0) for i in range(num_layers))
    return MixtralConfig(num_layers=num_layers, layer_rope=layout, layer_window=layout,
                         hidden_act="relu", router_input="attn", rms_norm_eps=1e-6, **sizes)


_SMALLTHINKER_21B = dict(
    vocab_size=151936, hidden_size=2560, num_heads=28, num_kv_heads=4, head_dim=128,
    intermediate_size=768, num_experts=64, experts_per_token=6, rope_theta=1500000.0,
    max_position_embeddings=16384, sliding_window=4096)

def _keye(num_layers: int, **sizes) -> MixtralConfig:
    """Keye-VL-2.0's text block (ISSUE 36): q/k norms, 16 indexer heads of
    64 in front of GQA, SiLU experts routed from their own normed input,
    softmax over the chosen (the published softmax over all, top-k,
    renormalised: the same numbers)."""
    return MixtralConfig(num_layers=num_layers, qk_norm=True, rms_norm_eps=1e-6, **sizes)


def _trinity(num_layers: int, **sizes) -> MixtralConfig:
    """Trinity-Mini's block (ISSUE 41, ``afmoe``): two leading dense layers,
    then expert layers routed by sigmoid scores with a selection bias, one
    shared expert beside the chosen; the attention's output gated, q/k norms,
    every sublayer's output normed; the embedding times sqrt(hidden); of
    every four layers three windowed with rotary, then one global with no
    position encoding (``layer_types``; the modelling code gives global
    layers no rotary)."""
    layout = tuple(int(i % 4 != 3) for i in range(num_layers))
    return MixtralConfig(num_layers=num_layers, num_dense_layers=2, layer_rope=layout, layer_window=layout,
                         qk_norm=True, attn_gate=True, sandwich_norm=True, embed_scale=True,
                         router_score="sigmoid", shared_experts=1, rms_norm_eps=1e-5, **sizes)


def _lfm2(layer_types: str, **sizes) -> MixtralConfig:
    """LFM2's mixture block (ISSUE 43, ``lfm2_moe``): a gated short convolution
    ("c") or grouped-query attention ("a") a layer in the published order, the
    operator the only difference between the two kinds; q/k norms before rotary
    in the attention layers; two leading dense layers, then experts routed by
    sigmoid scores with a selection bias, the chosen scores normalised
    (+ 1e-6) and no shared expert; a tied head."""
    return MixtralConfig(num_layers=len(layer_types), layer_conv=tuple(k == "c" for k in layer_types),
                         num_dense_layers=2, qk_norm=True, tie_word_embeddings=True, router_score="sigmoid",
                         route_scale=1.0, route_eps=1e-6, rms_norm_eps=1e-5, **sizes)


def _xing(num_layers: int, **sizes) -> MixtralConfig:
    """Xing4.0's block (ISSUE 47, ``xing4_0``): a latent attention under YaRN
    (the DeepSeek-V2 form its keys name), four residual streams mixed by
    Sinkhorn-projected hyper-connections around every block, two leading dense
    layers, then experts routed by sigmoid scores with a selection bias
    (``noaux_tc``, one group), the chosen scores normalised and times
    routed_scaling_factor 2, one shared expert; an untied head. Its next-token
    prediction module is not built: nothing here speculates with it."""
    return MixtralConfig(num_layers=num_layers, num_dense_layers=2, router_score="sigmoid", route_scale=2.0,
                         shared_experts=1, hc_mult=4, rms_norm_eps=1e-6, rope_theta=10000.0, **sizes)


def _granite(layer_types: str, **sizes) -> MixtralConfig:
    """Granite-4.0-H's block (ISSUE 50, ``granitemoehybrid``): a Mamba-2 mixer
    ("m") or grouped-query attention with NO position encoding ("a") a layer in
    the published order; the embedding times embedding_multiplier, both
    sublayers' outputs times residual_multiplier, attention scores scaled by
    attention_multiplier, the tied head's logits over logits_scaling; experts
    routed by a softmax over the chosen logits beside a shared gated MLP of
    2 x the experts' width in EVERY layer (no dense prefix)."""
    n = len(layer_types)
    return MixtralConfig(num_layers=n, layer_conv=tuple(2 * (k == "m") for k in layer_types), layer_rope=(0,) * n,
                         conv_kernel=4, shared_experts=2, tie_word_embeddings=True, rms_norm_eps=1e-5, **sizes)


def _kimi(layer_types: str, **sizes) -> MixtralConfig:
    """Kimi-Linear's block (ISSUE 54, ``kimi_linear``): Kimi Delta Attention
    ("k") or the latent attention with no query bottleneck and NO rotary ("m")
    a layer in the published order (three to one); the first layer's FFN a dense
    MLP, the others experts routed by sigmoid scores with a selection bias over
    one group, the chosen scores normalised and times routed_scaling_factor
    2.446, one shared expert; an untied head. ``experts_held``: a chip's share of
    the experts, under the whole router."""
    n = len(layer_types)
    return MixtralConfig(num_layers=n, layer_conv=tuple(3 * (k == "k") for k in layer_types), layer_rope=(0,) * n,
                         num_dense_layers=1, conv_kernel=4, router_score="sigmoid", route_scale=2.446,
                         shared_experts=1, rms_norm_eps=1e-5, rope_theta=10000.0, **sizes)


def _pangu(num_layers: int, **sizes) -> MixtralConfig:
    """openPangu-Ultra-MoE's block (ISSUE 56, ``pangu_ultra_moe``): the
    DeepSeek-V3 shape (a latent attention with a query bottleneck under plain
    rotary, leading dense layers of which a stage here holds ONE, experts routed
    by sigmoid scores, the 8 largest of all with no groups and no selection bias
    (``router_bias`` a leaf of zeros), the chosen scores normalised and times
    routed_scaling_factor 2.5, one shared expert, an untied head) with every
    sublayer's OUTPUT normed (``sandwich_norm``) and ONE next-token module
    (``num_nextn_predict_layers`` 1), with which a served engine drafts for
    itself. ``experts_held`` / ``vocab_published``: a chip's share of the experts
    and of the vocabulary."""
    return MixtralConfig(num_layers=num_layers, num_dense_layers=1, router_score="sigmoid", route_scale=2.5,
                         shared_experts=1, sandwich_norm=True, mtp_layers=1, rms_norm_eps=1e-5,
                         rope_theta=25600000.0, **sizes)


PRESETS: dict[str, MixtralConfig] = {
    "mixtral-test-tiny": MixtralConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        intermediate_size=96, num_experts=4, experts_per_token=2,
        max_position_embeddings=512,
    ),
    "mixtral-8x7b": MixtralConfig(
        vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8,
        intermediate_size=14336, num_experts=8, experts_per_token=2,
        rope_theta=1000000.0, max_position_embeddings=32768,
    ),
    # One period, a window shorter than the tests' sequences.
    "smallthinker-test-tiny": _smallthinker(
        4, vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=32, num_experts=8, experts_per_token=3, rope_theta=10000.0,
        max_position_embeddings=512, sliding_window=16),
    # PowerInfer/SmallThinker-21BA3B-Instruct is 52 layers, 21.9 GB of int8:
    # no single chip holds it and nothing here serves a pipeline of paged
    # stages yet. This is its first stage of four: 16 layers = 4 whole
    # periods, every width, all 64 experts and the whole vocabulary as
    # published, which one v5e serves.
    "smallthinker-21b-a3b-stage16": _smallthinker(16, **_SMALLTHINKER_21B),
    # topk shorter than the tests' sequences, two tiles' worth of queries.
    "keye-test-tiny": _keye(
        2, vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=32, num_experts=8, experts_per_token=2, rope_theta=10000.0,
        max_position_embeddings=512, indexer_heads=4, indexer_head_dim=16, indexer_topk=24),
    # Kwai-Keye/Keye-VL-2.0-30B-A3B's text decoder is 48 layers, 30 GB of
    # int8: this is the first of six pipeline stages of 8, every width, all
    # 128 experts and the whole vocabulary as published, which one v5e
    # serves; the vision tower is not built.
    "keye-vl2-30b-a3b-stage8": _keye(
        8, vocab_size=151936, hidden_size=2048, num_heads=32, num_kv_heads=4, head_dim=128,
        intermediate_size=768, num_experts=128, experts_per_token=8, rope_theta=10000000.0,
        max_position_embeddings=262144, indexer_heads=16, indexer_head_dim=64, indexer_topk=2048),
    # The stage's own shape at toy widths: 2 dense + 6 expert layers, two
    # periods of S,S,S,F (the first straddles the two stacks), a window
    # shorter than the tests' sequences; route_scale sqrt(2) for its top-2 as
    # the published 2.826 is sqrt(8) for top-8: the routed sum of unit experts
    # has unit size at either.
    "trinity-test-tiny": _trinity(
        8, vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=32, dense_intermediate_size=96, num_experts=8, experts_per_token=2,
        route_scale=1.414, rope_theta=10000.0, max_position_embeddings=512, sliding_window=16),
    # arcee-ai/Trinity-Mini is 32 layers, 26.5 GB of int8: this is the first
    # of four pipeline stages of 8 (the 2 leading dense layers + 6 expert
    # layers: two whole periods), every width, all 128 experts and the whole
    # vocabulary as published, which one v5e serves.
    "trinity-mini-stage8": _trinity(
        8, vocab_size=200192, hidden_size=2048, num_heads=32, num_kv_heads=4, head_dim=128,
        intermediate_size=1024, dense_intermediate_size=6144, num_experts=128, experts_per_token=8,
        route_scale=2.826, rope_theta=10000.0, max_position_embeddings=131072, sliding_window=2048),
    # The published first eight layers at toy widths: the prefix holds
    # convolution layers only, and the body runs of them between attention layers.
    "lfm2-test-tiny": _lfm2(
        "ccacccac", vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=32, dense_intermediate_size=96, num_experts=8, experts_per_token=2,
        rope_theta=10000.0, max_position_embeddings=512),
    # LiquidAI/LFM2-8B-A1B whole: 24 layers (18 convolutions, attention at 2, 6,
    # 10, 14, 18, 21), every width, all 32 experts and the whole vocabulary as
    # published: 8.5 GB of int8, which one v5e serves beside a 6-layer page pool.
    "lfm2-8b-a1b": _lfm2(
        "ccacccacccacccacccaccacc", vocab_size=65536, hidden_size=2048, num_heads=32, num_kv_heads=8,
        head_dim=64, intermediate_size=1792, dense_intermediate_size=7168, num_experts=32, experts_per_token=4,
        rope_theta=1000000.0, max_position_embeddings=128000),
    # The stage's own shape at toy widths: 2 dense + 2 expert layers, a latent of
    # 32 beside a rotary key of 8 (a pool row of 40 in 128 lanes), YaRN by 4 over
    # an original 64 positions, so the tests' sequences pass it.
    "xing4-test-tiny": _xing(
        4, vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=4, head_dim=24, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=32,
        dense_intermediate_size=96, num_experts=8, experts_per_token=2, max_position_embeddings=512,
        rope_scaling={"type": "yarn", "factor": 4, "original_max_position_embeddings": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}),
    # XingChen-AGI/Xing4.0-29B-A4B is 40 layers, 30.1 GB of int8: this is the first
    # of four pipeline stages of 10 (the 2 leading dense layers + 8 expert layers),
    # every width, all 64 experts and the whole vocabulary as published, which one
    # v5e serves beside a pool of 640-lane latent rows.
    "xing4.0-stage10": _xing(
        10, vocab_size=131072, hidden_size=3584, num_heads=32, num_kv_heads=32, head_dim=192, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, intermediate_size=1024,
        dense_intermediate_size=9216, num_experts=64, experts_per_token=4, max_position_embeddings=262144,
        rope_scaling={"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}),
    # The stage's own shape at toy widths: runs of Mamba-2 layers around one
    # attention layer, 4 heads of 8 with a state of 16, a block of the scan (8)
    # shorter than the tests' sequences, 8 experts top-3 beside the shared MLP.
    "granite4-test-tiny": _granite(
        "mmamm", vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=32,
        num_experts=8, experts_per_token=3, max_position_embeddings=512, ssm_heads=4, ssm_head_dim=32, ssm_state=16,
        ssd_block=8, embedding_multiplier=12.0, attention_multiplier=1 / 16, residual_multiplier=0.22,
        logits_scaling=16.0),
    # ibm-granite/granite-4.0-h-small is 40 layers (36 Mamba-2, attention at 5,
    # 15, 25, 35), 31.8 GB of int8: no chip holds it. This is the first of four
    # pipeline stages of 10 (one period: m m m m m a m m m m), every width, all 72
    # experts and the whole vocabulary as published: 8.77 GB, which one v5e serves
    # beside a page pool of ONE layer and a state store of 38.7 MB a slot. The
    # scan's blocks are 128 tokens, the side of the v5e's matrix unit (the
    # published mamba_chunk_size 256 is the training kernel's; any block gives
    # the recurrence's numbers).
    "granite-4.0-h-small-stage10": _granite(
        "mmmmmammmm", vocab_size=100352, hidden_size=4096, num_heads=32, num_kv_heads=8, head_dim=128,
        intermediate_size=768, num_experts=72, experts_per_token=10, max_position_embeddings=131072,
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssd_block=128, embedding_multiplier=12.0,
        attention_multiplier=0.0078125, residual_multiplier=0.22, logits_scaling=16.0),
    # The stage's own shape at toy widths: dense K, then K K M K K K M (five runs in
    # eight layers), 4 heads of 32 with a block of 8 tokens (halved at 4, 2 and
    # 1), a latent of 32 beside 8 shared lanes, 8 experts top-2 of which this
    # "chip" holds the first 4, beside a shared one.
    "kimi-linear-test-tiny": _kimi(
        "kkkmkkkm", vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=4, head_dim=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=32, dense_intermediate_size=96,
        num_experts=8, experts_per_token=2, experts_held=4, max_position_embeddings=512,
        kda_heads=4, kda_head_dim=32, kda_block=8, kda_rank=16),
    # moonshotai/Kimi-Linear-48B-A3B-Instruct is 27 layers, 48 GB of int8: no chip
    # holds it. This is ONE of the two chips that share each layer of the first of
    # four pipeline stages (8, 8, 8, 3 layers): layers 1-8 = two whole periods (K K K
    # M K K K M, layer 1 dense), every width and the whole vocabulary as published,
    # experts 0-127 of the 256 under the whole router (the other chip holds 128-255
    # and everything else twice): 7.9 GB, which one v5e serves beside a latent pool
    # of TWO layers and a state store of 13.5 MB a slot.
    "kimi-linear-48b-a3b-stage8": _kimi(
        "kkkmkkkm", vocab_size=163840, hidden_size=2304, num_heads=32, num_kv_heads=32, head_dim=192, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, intermediate_size=1024,
        dense_intermediate_size=9216, num_experts=256, experts_per_token=8, experts_held=128,
        max_position_embeddings=1048576, kda_heads=32, kda_head_dim=128, kda_block=64),
    # The share's own shape at toy widths: 1 dense + 2 expert layers and the
    # next-token module, a latent of 32 beside a rotary key of 8 under a query
    # bottleneck of 32, 8 experts top-2 of which this "chip" holds the first 4, a
    # vocabulary of 512 of which it holds the first 256.
    "openpangu-test-tiny": _pangu(
        3, vocab_size=256, vocab_published=512, hidden_size=64, num_heads=4, num_kv_heads=4, head_dim=24,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=32,
        dense_intermediate_size=96, num_experts=8, experts_per_token=2, experts_held=4, max_position_embeddings=512),
    # FreedomIntelligence/openPangu-Ultra-MoE-718B is 61 layers of 256 experts, 738 GB
    # of int8: a layer's experts alone are 12 GB. This is ONE of the sixteen chips
    # that share each layer of a first pipeline stage: layer 0 (one of the three
    # leading dense layers) + six expert layers + the next-token module, every
    # width, 128 heads and the 256-wide router as published, experts 0-15 of 256
    # and rows 0-19,199 of the 153,600-row vocabulary (two chips' rows: the
    # guide's floor of an eighth): 8.2 GB beside a latent pool of EIGHT layers.
    "openpangu-ultra-moe-718b-stage7": _pangu(
        7, vocab_size=19200, vocab_published=153600, hidden_size=7680, num_heads=128, num_kv_heads=128, head_dim=192,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        intermediate_size=2048, dense_intermediate_size=18432, num_experts=256, experts_per_token=8, experts_held=16,
        max_position_embeddings=131072),
}


# -- what serving a mixture takes (models/family.py, ISSUE 58) -----------------
# Rows of a step's fetch block beside its tokens, one a name, in the order the step
# programs put them there (serving/engine.py reduces the experts' rows, the rest is
# ``forward_paged`` / ``forward_ragged``'s auxiliary behind them: ``split_aux``).
# A mixed step counts the rows each expert multiplied (ISSUE 28), the experts read
# and the visits the expert kernels made (ISSUE 42); a decode chunk carries the last
# two, means over the steps it ran.
MIXED_STATS = ("expert_tokens_max", "expert_tokens_mean", "experts_read", "expert_visits")
# With an indexer (ISSUE 36): the cached tokens its rows' queries could see and the
# tokens whose keys and values their attention read, summed over queries and layers
# (float32: exact to 2**24, a part in 10**7 beyond).
DSA_STATS = ("dsa_context_tokens", "dsa_kv_tokens_read")
# With windowed layers (ISSUE 41), or a pool of the caching layers alone (ISSUE 43):
# the pages those layers read and the pages their rows' contexts hold.
WINDOW_STATS = ("window_pages_read", "context_pages")
# With a latent pool (ISSUE 47): the pages of latent the rows' tiles walked, the pages
# their contexts hold, and of the first what the one-query rows walked (``latent_pages``).
LATENT_STATS = ("latent_pages_read", "context_pages", "latent_row_pages_read")
# With Mamba-2 layers (ISSUE 50): the blocks the chunk kernel walked over all state
# layers (0 in a decode chunk) and the rows whose matrix state the one-token update
# read and wrote in each; the same two for Kimi Delta Attention layers (ISSUE 54). And
# (ISSUE 65) the plan's static bound of those visits over the same layers
# (``ops/ssd.max_visits``; 0 in a decode chunk): the kernel's grid runs the live visits,
# so blocks over bound is the share of the plan's length that was a grid step.
SSD_STATS = ("ssd_blocks", "state_rows", "chunk_visits_bound")
KDA_STATS = ("kda_blocks", "state_rows", "chunk_visits_bound")
# With a next-token module (ISSUE 56): the rounds of a row that verified a draft, the
# drafts accepted, and the tokens those rounds emitted (1 or 2 a round): each a sum.
MTP_STATS = ("mtp_drafted", "mtp_accepted", "mtp_tokens")


def split_aux(cfg: MixtralConfig, aux):
    """``forward_paged`` / ``forward_ragged``'s third result -> (the rows each expert
    multiplied (expert layers, E), the step's further counts in the stat names' order)."""
    if cfg.indexer_topk:  # the pair (the FFN's, the attention seam's (L, 2))
        return aux[0], jnp.sum(aux[1], axis=0)
    if counts_window_pages(cfg):  # the pair (the FFN's, ``counted_window_pages`` and the state layers' counts)
        return aux
    return aux, jnp.zeros((0,), jnp.float32)


def _refuses(cfg: MixtralConfig) -> dict:
    """``Family.refuses``: what this model's path does not carry, each reason as it is served."""
    text_alone = "it is a text model; image input has no path, serve text"
    if cfg.indexer_topk:  # ISSUE 36
        out = {"spec_draft": "speculative decoding has no verify pass through the sparse-attention indexer; serve it "
                             "without --spec-draft",
               "vision_model": "its vision tower is not built (the published config gives no width for it); image "
                               "input has no path, serve text",
               "image_part": "its vision tower is not built; image input has no path, send text", "int4": INT8_ALONE}
    elif cfg.layer_conv is not None:  # what a carried state rules out (ISSUE 43)
        out = {"spec_draft": "speculative decoding would need each convolution layer's state at the last ACCEPTED "
                             "token, and a rejected draft has moved it on; serve it without --spec-draft",
               "vision_model": text_alone, "int4": INT8_ALONE}
    elif cfg.num_dense_layers:  # ISSUE 41
        out = {"spec_draft": "speculative decoding has no verify pass over its two stacks of layers; serve it without "
                             "--spec-draft",
               "vision_model": text_alone, "int4": INT8_ALONE}
    else:
        out = {}
    if cfg.kv_lora_rank:  # ISSUE 47
        own = ("it drafts for itself with its own next-token module, in both step programs" if cfg.mtp_layers else
               "its own next-token-prediction module is not part of this preset (num_nextn_predict_layers 0)")
        out["spec_draft"] = ("speculative decoding with a separate draft model or prompt lookup has no verify pass over "
                             f"the latent pool ({own}); serve it without --spec-draft")
    # No mesh carries these yet: a stated shape is refused, and left to the default the
    # model is served on one device (``mesh_axes`` and /debug/status say {"tp": 1}).
    carries = ("carries the per-slot state store" if cfg.layer_conv is not None else
               "carries the sparse-attention indexer and its key cache" if cfg.indexer_topk else
               "lays out its next-token module and the drafts' carried state" if cfg.mtp_layers else
               # (ISSUE 41: ``param_specs`` lays out neither stack)
               "lays out its leading dense layers' stack, its attention gate and its shared expert"
               if cfg.num_dense_layers else None)
    if carries:
        out["mesh"] = f"no mesh ({{shape}}) {carries} yet; serve it on one device"
    return out


def _status(cfg: MixtralConfig, view) -> dict:
    """``Family.status``: a mixture's /debug/status blocks. With a state store
    ``state_store``, ``cache_layers``, the two counters and how its scan is run
    (``ssd`` | ``kda``); with an indexer ``dsa``; with a latent attention
    ``latent_pool``; with a next-token module ``mtp`` and ``vocab``; always ``router``,
    ``shared_experts``, ``dense_prefix``, and ``cache_layers`` of windowed layers."""
    from inference_gateway_tpu.ops import sparse_attention
    from inference_gateway_tpu.ops import ssd as ssd_ops
    from inference_gateway_tpu.utils.platform import on_tpu

    out, itemsize = {}, jnp.dtype(view.dtype).itemsize
    if cfg.layer_conv is not None:
        out = {"state_store": {"slots": view.max_slots, "layers": cfg.conv_layers,
                               "bytes": view.max_slots * cfg.state_bytes_per_slot},
               "cache_layers": {"full": cfg.cache_layers,
                                "kda" if cfg.kda else "mamba2" if cfg.mamba2 else "conv": cfg.conv_layers},
               "state_resets": view.state_resets, "prefix_hits_declined": view.prefix_hits_declined}
    def one_token(plan_of, heads, rows, lanes):
        """The one-token update's plan for the engine's store (ISSUE 63): the path
        by the store's static shape and the platform, and what a visit moves."""
        plan = plan_of((cfg.conv_layers, view.max_slots, heads, rows, lanes))
        return {"one_token_rows": plan.pop("path"), "one_token_update": plan}

    def chunk_form(name):
        """Which chunk form runs, and (ISSUE 65) where the kernel's visit axis ends."""
        if on_tpu():
            return {"rows_of_more_than_one_token": f"{name}_chunk_kernel", "grid": "live visits"}
        return {"rows_of_more_than_one_token": f"{name}_chunk_twin"}

    if cfg.mamba2:   # how the Mamba-2 scan is done (ISSUE 50; static)
        out["ssd"] = {"heads": cfg.ssm_heads, "state": [cfg.ssm_head_dim, cfg.ssm_state], "block": cfg.ssd_block,
                      **chunk_form("ssd"),
                      **one_token(ssd_ops.update_plan, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)}
    if cfg.kda:   # how the delta rule is run (ISSUE 54; static)
        out["kda"] = {"heads": cfg.kda_heads, "state": [cfg.kda_head_dim, cfg.kda_head_dim], "block": cfg.kda_block,
                      "decay": "a channel", "state_bytes_per_slot_per_layer": cfg.kda_inner * cfg.kda_head_dim * 4,
                      **chunk_form("kda"),
                      **one_token(kda_ops.update_plan, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)}
    if cfg.indexer_topk:   # its sizes, and how the selection and the read are done (static)
        row_tokens = view.page_cfg.max_pages_per_slot * view.page_cfg.page_size
        walk = sparse_attention.one_query_read(cfg.indexer_topk, row_tokens) == "walk"
        out["dsa"] = {
            "topk": cfg.indexer_topk, "indexer_heads": cfg.indexer_heads,
            "indexer_head_dim": cfg.indexer_head_dim,
            "index_cache_bytes_per_token": cfg.num_layers * cfg.indexer_head_dim * itemsize,
            "short_context": "a query that sees topk tokens or fewer keeps them all, by the same path (no plain-attention branch)",
            # Static a program: a row of the page table against WALK_UNDER x topk (ISSUE 45).
            "one_query_row": {"read": "walk" if walk else "gather", "row_tokens": row_tokens,
                              "walk_under": sparse_attention.WALK_UNDER,
                              "walk_up_to_tokens": sparse_attention.WALK_UNDER * cfg.indexer_topk},
            "select": {"one_query_row": ("mask by the exact k-th largest score (32 counting passes, ties by position)"
                                         if walk else "lax.top_k over the row's scores"),
                       "prompt_chunk": "exact k-th largest by 32 counting passes, ties by position, as a mask"},
            "read": {"one_query_row": ("whole pages under the mask, by the paged decode kernel (every page the row holds)"
                                       if walk else "token gather of the chosen keys and values (topk tokens)"),
                     "prompt_chunk": "tiles of 128 queries over every block of keys up to the tile's last position, masked"},
        }
    if cfg.kv_lora_rank:   # the pool's row and bytes a token, and how it is read (static)
        out["latent_pool"] = {
            "row_values": cfg.latent_width, "row_lanes": cfg.latent_row,
            "row": f"[{cfg.kv_lora_rank} latent, {cfg.qk_rope_head_dim} rotary key, "
                   f"{cfg.latent_row - cfg.latent_width} zeros to the 128-lane grid]",
            "bytes_per_token_per_layer": cfg.latent_row * itemsize,
            "bytes_per_token": cfg.cache_layers * cfg.latent_row * itemsize, "layers": cfg.cache_layers,
            "arrays": [MLA_POOL], "heads": cfg.num_heads, "value_dim": cfg.kv_lora_rank,
            "read": "absorbed: every head's query in the latent's space against one key row, "
                    "the row's first value_dim values the value, a page fetched once a visit",
            "residual_streams": cfg.hc_mult or 1,
        }
    if cfg.mtp_layers:   # the module (static), what its rounds have come to, the share of the vocabulary held
        mtp = {"layers": cfg.mtp_layers, "drafts": "one token a row a round, from the model's own next-token module",
               "verified": "as the row's second query, in both step programs; accepted by speculative.spec_accept"}
        if view.scheduler is not None:
            drafted = view.scheduler.spec_slot_rounds
            mtp.update(drafted=drafted, accepted=view.scheduler.spec_accepted,
                       tokens_per_round=round(view.scheduler.spec_emitted / drafted, 3) if drafted else None)
        out.update(mtp=mtp, vocab={"held": cfg.vocab_size, "published": cfg.vocab_published or cfg.vocab_size})
    out.update({"router": {"score": cfg.router_score, "experts": cfg.num_experts,
                           # (ISSUE 54) a chip's share: the router chooses among ``experts``, this
                           # process computes ``experts_held`` of them and nothing in place of the rest
                           "experts_held": cfg.held_experts, "experts_first": cfg.experts_first,
                           "experts_per_token": cfg.experts_per_token, "route_scale": cfg.route_scale,
                           "chosen_by": "score + bias" if cfg.router_score == "sigmoid" else "logit",
                           "weights": ("the chosen scores, normalised" if cfg.router_score == "sigmoid"
                                       else "softmax over the chosen logits")},
                "shared_experts": cfg.shared_experts, "dense_prefix": cfg.num_dense_layers})
    if counts_window_pages(cfg) and cfg.layer_conv is None:   # (with a state store: said above)
        windowed = windowed_layers(cfg)
        out["cache_layers"] = {"window": windowed, "full": cfg.num_layers - windowed}
    return out


def family(cfg: MixtralConfig) -> Family:
    """What serving ``cfg`` takes: the one place that names this config's fields
    for ``serving/``."""
    state, indexer, latent = cfg.layer_conv is not None, bool(cfg.indexer_topk), bool(cfg.kv_lora_rank)
    more = ((DSA_STATS if indexer else ())
            + ((LATENT_STATS if latent else WINDOW_STATS) if counts_window_pages(cfg) else ())
            + (SSD_STATS if cfg.mamba2 else ()) + (KDA_STATS if cfg.kda else ()) + (MTP_STATS if cfg.mtp_layers else ()))
    return Family(
        module=sys.modules[__name__], experts=True, pool="latent" if latent else "kv+index" if indexer else "kv",
        row_writes=not latent,
        state_store=state, mixed_alone=state or indexer or latent, round_tokens=2 if cfg.mtp_layers else 1,
        refuses=_refuses(cfg), chunk_stats=MIXED_STATS[2:] + more, mixed_stats=MIXED_STATS + more,
        attention_layers=attention_layers(cfg.cache_layers, cfg.sliding_window,
                                          min(windowed_layers(cfg), cfg.cache_layers)),
        status=partial(_status, cfg))
