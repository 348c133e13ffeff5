"""Llama-family decoder (TinyLlama, Llama-2, Llama-3/3.1) in pure JAX.

TPU-first design decisions:
- Parameters are a flat pytree of arrays with layers **stacked** on a
  leading axis, walked with ``lax.scan`` — one trace regardless of depth,
  fast compiles, and sharding annotations apply uniformly to every layer.
- One jitted ``forward`` serves prefill (T = padded prompt bucket) and
  decode (T = 1) against a contiguous KV cache with static shapes; ragged
  batches are handled by masks, never by dynamic shapes.
- bf16 weights/activations, fp32 softmax/norm statistics, fp32 matmul
  accumulation (``preferred_element_type``) — the MXU recipe.

This is the serving model behind the ``tpu`` provider (the capability the
reference delegates to Ollama/llama.cpp upstreams,
reference providers/registry/registry.go:143-208).
"""

from __future__ import annotations

import sys
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from inference_gateway_tpu.models.family import INT8_ALONE, Family, attention_layers
from inference_gateway_tpu.ops.attention import causal_prefill_mask, decode_mask, gqa_attend
from inference_gateway_tpu.ops.norms import rms_norm
from inference_gateway_tpu.ops.pool_write import write_rows
from inference_gateway_tpu.ops.quant import qmatmul
from inference_gateway_tpu.ops.rope import apply_rope, rope_cos_sin, rope_inv_freq


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    intermediate_size: int = 5632
    head_dim: int | None = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    qkv_bias: bool = False  # Qwen2-style attention projections
    hidden_act: str = "silu"  # "silu" (Llama/Qwen) | "gelu_tanh" (Gemma)
    sliding_window: int | None = None  # Mistral-style windowed attention
    norm_offset: bool = False  # Gemma-style RMSNorm weight = (1 + w)
    embed_scale: bool = False  # Gemma scales embeddings by sqrt(hidden)
    qk_norm: bool = False  # RMSNorm over head_dim on q and k before rotary (leaves q_norm, k_norm)
    # ISSUE 41: the attention's output times sigmoid(h @ attn_gate) before the
    # output projection; and each sublayer's OUTPUT normed before it is added
    # (leaves post_attn_norm, post_ffn_norm), beside the norms of its input.
    attn_gate: bool = False
    sandwich_norm: bool = False
    # Per-layer attention kinds (ISSUE 28), one 0/1 flag a layer, or None
    # for a model whose layers are all alike: layer_rope 0 is a layer with
    # no position encoding; layer_window 0 is a global layer of a model
    # whose other layers see only ``sliding_window`` keys.
    layer_rope: tuple | None = None
    layer_window: tuple | None = None
    # Granite's four multipliers (ISSUE 50), each at the value that changes
    # nothing: the embedding times embedding_multiplier; attention scores
    # scaled by attention_multiplier in place of head_dim ** -0.5 (None);
    # both sublayers' outputs times residual_multiplier before they join the
    # stream; the logits divided by logits_scaling.
    embedding_multiplier: float = 1.0
    attention_multiplier: float | None = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # EvaByte (ISSUE 61). ``eva_window``: EVA attention (ops/eva_attention.py), exact
    # keys inside the query's own window of that many tokens and one learned summary a
    # ``eva_chunk`` tokens of every window behind it (leaves eva_mu, eva_phi; the pool's
    # rows are ``cached_rows``, not one a token). ``num_pred_heads``: the head is that many
    # heads of ``vocab_size`` columns side by side; the first predicts the next token.
    # (The model's ``fp32_skip_add``, a sublayer's output summed into the stream in float32
    # and rounded once, is what adding two bf16 arrays gives, bit for bit: no field.)
    eva_window: int | None = None
    eva_chunk: int = 16
    num_pred_heads: int = 1
    # Stored as a hashable tuple of (key, value) pairs so the config can be
    # a jit static argument; accepts a dict at construction.
    rope_scaling: Any = None

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        for name in ("layer_rope", "layer_window"):
            flags = getattr(self, name)
            if flags is not None:
                assert len(flags) == self.num_layers, (name, len(flags), self.num_layers)
                object.__setattr__(self, name, tuple(int(bool(f)) for f in flags))

    @property
    def rope_scaling_dict(self) -> dict | None:
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads


Params = dict[str, Any]

_ACT = {
    "silu": jax.nn.silu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}


def _nw(w, cfg: "LlamaConfig"):
    """Norm weight convention: Gemma stores (w - 1)."""
    return w + 1 if cfg.norm_offset else w


def _embed(params: Params, cfg: "LlamaConfig", tokens):
    """The tokens' rows of the embedding, times Gemma's sqrt(hidden) or
    Granite's embedding_multiplier where the model has one."""
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _head(params: Params, cfg: "LlamaConfig", x):
    """Float32 logits of normed states ``x``: the tied or the untied head,
    divided by logits_scaling where the model has one."""
    if cfg.tie_word_embeddings:
        logits = (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)
    else:
        logits = qmatmul(x, params["lm_head"]).astype(jnp.float32)
        if cfg.num_pred_heads > 1:   # the heads lie side by side: the first is the next token's
            logits = logits[..., :cfg.vocab_size]
    return logits if cfg.logits_scaling == 1.0 else logits / cfg.logits_scaling


def _scaled(y, cfg: "LlamaConfig"):
    """A sublayer's output as it joins the stream: times residual_multiplier."""
    return y if cfg.residual_multiplier == 1.0 else y * jnp.asarray(cfg.residual_multiplier, y.dtype)


def _rope_qk(q, k, cos, sin, cfg: "LlamaConfig", li, lp=None):
    """Rotary on q and k; in a model with NoPE layers only where layer
    ``li``'s flag is set (the rotation is elementwise: both are cheap).
    With ``cfg.qk_norm`` each head is RMS-normed first (``lp``'s q_norm,
    k_norm)."""
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    rq, rk = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if cfg.layer_rope is None:
        return rq, rk
    on = jnp.asarray(cfg.layer_rope, bool)[li]
    return jnp.where(on, rq, q), jnp.where(on, rk, k)


def _attend_by_window(cfg: "LlamaConfig", li, attend):
    """``attend(window)`` for layer ``li``. ``window`` is a static
    argument of the dense prefill paths, so a model that mixes windowed
    and global layers carries one instance of each under a ``lax.cond``
    on the layer's flag; a model whose layers are alike traces one. (The
    paged kernels take ``_window_operand`` instead.)"""
    if cfg.layer_window is None or not cfg.sliding_window:
        return attend(cfg.sliding_window)
    return jax.lax.cond(jnp.asarray(cfg.layer_window, bool)[li],
                        lambda: attend(cfg.sliding_window), lambda: attend(None))


def _window_operand(cfg: "LlamaConfig", li) -> dict:
    """Layer ``li``'s window as the paged kernels take it (the ragged one
    since ISSUE 34, the decode one since ISSUE 37). A model whose layers
    are alike hands it statically (``window=``: none pays no mask); one
    that mixes windowed and global layers hands it as an operand
    (``window_dyn=``), so each of its step programs traces and lowers ONE
    instance of its kernel, not one a kind."""
    if cfg.layer_window is None or not cfg.sliding_window:
        return {"window": cfg.sliding_window}
    from inference_gateway_tpu.ops.paged_attention import GLOBAL_WINDOW

    return {"window_dyn": jnp.where(jnp.asarray(cfg.layer_window, bool)[li],
                                    cfg.sliding_window, GLOBAL_WINDOW).astype(jnp.int32)}


def window_pages(layers: int, window: int, page_size: int, first_len, last_len):
    """What a step's ``layers`` windowed layers read of the pool, and what
    their rows' contexts hold, in pages over all of them: a row whose
    queries see lengths first_len..last_len (0 = no row) reads the pages
    from the one holding position first_len - window on, as both paged
    kernels do. Returns float32 (2,): window_pages_read, context_pages."""
    live = last_len > 0
    held = (last_len + page_size - 1) // page_size
    skipped = jnp.maximum(first_len - window, 0) // page_size
    read = jnp.sum(jnp.where(live, held - skipped, 0))
    return layers * jnp.stack([read, jnp.sum(jnp.where(live, held, 0))]).astype(jnp.float32)


def _attn_out(attn, h, lp: Params, cfg: "LlamaConfig"):
    """The attention sublayer's residual contribution: ``attn`` (..., Hq*D)
    through the output projection; with ``cfg.attn_gate`` first multiplied,
    element by element, by a sigmoid gate read from the sublayer's normed
    input ``h``; with ``cfg.sandwich_norm`` the projection's output normed."""
    if cfg.attn_gate:
        with jax.named_scope("attn.gate"):
            gate = jax.nn.sigmoid(qmatmul(h, lp["attn_gate"]).astype(jnp.float32))
            attn = (attn.astype(jnp.float32) * gate).astype(attn.dtype)
    out = qmatmul(attn, lp["wo"])
    if cfg.sandwich_norm:
        out = rms_norm(out, _nw(lp["post_attn_norm"], cfg), cfg.rms_norm_eps)
    return out


def _ffn_out(y, lp: Params, cfg: "LlamaConfig"):
    """The FFN sublayer's residual contribution, normed under ``sandwich_norm``."""
    return rms_norm(y, _nw(lp["post_ffn_norm"], cfg), cfg.rms_norm_eps) if cfg.sandwich_norm else y


def _layer_groups(params: Params, ffn, unscanned: tuple, prefix, num_layers: int, kinds=None) -> list:
    """The scanned groups of a layer stack, in order, as (leaves, ffn, first
    layer index, layers): one group for a model whose layers are alike; two
    where a ``prefix`` of layers with an FFN of their own leads (ISSUE 41:
    ``{"layers": leaves with their own leading axis, "ffn": the seam}``). The
    layer index runs on across the groups: one page pool, one page table.

    A per-layer kind that asks for it (``kinds["runs"]``, ISSUE 50) has its
    body cut into RUNS of one kind, a scan each: no scanned body then holds
    both kinds, so no ``lax.cond`` stands around a state store too large to be
    copied (``_mix_by_kind``). A run's scanned leaves are slices of the body's
    (norms and a router: the matrices are read whole at an index, ``unscanned``)."""
    lead = jax.tree.leaves(prefix["layers"])[0].shape[0] if prefix else 0
    scanned = _scan_layers(params, unscanned)
    groups = [(prefix["layers"], prefix["ffn"], 0, lead)] if prefix else []
    if kinds is None or not kinds.get("runs"):
        return groups + [(scanned, ffn, lead, num_layers - lead)]
    flags, first = kinds["state"], lead
    for li in range(lead + 1, num_layers + 1):
        if li == num_layers or flags[li] != flags[first]:
            groups.append((jax.tree.map(lambda a: a[first - lead:li - lead], scanned), ffn, first, li - first))
            first = li
    return groups


def _project_qkv(h, ap: Params, li, cfg: "LlamaConfig", cos, sin, cache: Params):
    """The attention operator's projections of a layer's normed input h (B, T,
    H): ``ap`` holds its leaves. Returns (q, k, v by head after norm and
    rotary; the keys' and the values' rows as the pool holds them)."""
    B, T, _ = h.shape
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = qmatmul(h, ap["wq"])
    k = qmatmul(h, ap["wk"])
    v = qmatmul(h, ap["wv"])
    if cfg.qkv_bias:
        q = q + ap["bq"]
        k = k + ap["bk"]
        v = v + ap["bv"]
    q = q.reshape(B, T, Hq, D)
    k = k.reshape(B, T, Hkv, D)
    v = v.reshape(B, T, Hkv, D)
    q, k = _rope_qk(q, k, cos, sin, cfg, li, ap)
    if cfg.attention_multiplier is not None:
        # The kernels scale scores by head_dim ** -0.5: the queries carry the rest.
        q = q * jnp.asarray(cfg.attention_multiplier * D ** 0.5, q.dtype)
    return (q, k, v, k.reshape(B, T, Hkv * D).astype(cache["k"].dtype),
            v.reshape(B, T, Hkv * D).astype(cache["v"].dtype))


def _write_kv(ck, cv, qkv, write_idx, plane, flat: int, total: int, skip=None, mesh=None):
    """A step's keys and values into ``plane`` of the flat pools (the layer
    index, but for ``kinds``); nothing where ``skip`` is set. Returns the
    pools and the indices written. Both pools in one ``write_rows`` (ISSUE 62:
    on the chip a copy a run of rows, where its dispatch says so; pools sharded
    over a ``mesh`` keep the scatter)."""
    # Per-layer offset; rows that were OOB within the layer (== flat,
    # the drop convention) must stay OOB for the WHOLE buffer, not
    # land in layer li+1's first page.
    with jax.named_scope("kv.write"):
        w_idx = jnp.where(write_idx >= flat, total, write_idx + plane * flat)
        if skip is not None:
            w_idx = jnp.where(skip, total, w_idx)
        ck, cv = write_rows((ck, cv), (qkv[3], qkv[4]), w_idx, mesh=mesh)
        return ck, cv, w_idx


def _flat_index(write_idx, plane, flat: int, total: int):
    """``_write_kv``'s indices for a mixer that writes its own pool."""
    return jnp.where(write_idx >= flat, total, write_idx + plane * flat)


def _owns_pool(mixer) -> bool:
    """An attention seam that projects its own queries and owns the ONLY pool
    (ISSUE 47: ``cache[mixer["pool"]]``, no ``k`` and no ``v``)."""
    return mixer is not None and "pool" in mixer


def _block_in(residual, x, lp: Params, block: str):
    """The residual seam's read (ISSUE 47): what block ``block`` ("attn" |
    "ffn") of a layer reads of the stream, and what its write-back needs.
    ``residual`` None is the one stream of ``hidden_size``, read whole."""
    return (x, None) if residual is None else residual["read"](x, lp, block)


def _block_out(residual, x, y, maps):
    """The seam's write-back of a block's output ``y``: ``x + y`` for None."""
    return x + y if residual is None else residual["write"](x, y, maps)


def _mix_by_kind(kinds: dict, group: tuple, li, h, ck, cv, store: dict, ops):
    """Layer ``li``'s token mixer in a model whose layers are of two KINDS
    (ISSUE 43; ``kinds`` is the seam of ``forward_paged_impl``): an attention
    layer projects, writes its plane of the pool and calls the kernel
    (``ops``: the skeleton's own ``project``, ``write`` and ``attend``); a state layer
    writes no key, calls no kernel, and reads and writes its entry of the
    slot-indexed ``store`` (name -> (state layers, slots, ...)). Each operator's leaves and cache are stacks of
    their own, read at the layer's ORDINAL among its kind, not at the layer
    index. One scanned body holds both kinds under ``lax.cond``s on the
    layer's flag, so a step program holds one instance of its kernel; a
    ``group`` (first layer, layers) of one kind alone holds no branch.

    The pools never pass THROUGH a ``cond``: the chip's compiler copies a
    pool that one branch updates and the other hands back (0.8 GB a side an
    attention layer: the first tree's decode step read 91 ms on the chip,
    PERF.md section 6, PR 43). So the first ``cond`` projects (queries, keys
    and values, or nothing), the write is outside it with a state layer's
    indices past the pool (dropped), and the second reads the pools and
    either attends or runs the state operator. The STORE does pass through the
    second: a model whose store is too large for that has its layers grouped
    by runs of one kind (``_layer_groups``). Returns (the residual
    contribution, the pools, the store).

    An attention seam that OWNS the pool beside the kinds (ISSUE 54: ``project``
    is None and ``attend(h, leaves, li, plane, pool) -> (contribution, pool)``
    is the seam's own projection, write and read): the layers are grouped by
    runs, so an attention layer is that one call."""
    flags = kinds["state"]
    ordinal = jnp.asarray([sum(f == flags[l] for f in flags[:l]) for l in range(len(flags))], jnp.int32)[li]
    at = lambda stack: jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, ordinal, 0, keepdims=False), stack)
    project, write, attend = ops

    def state_layer(store):
        B, T, H = h.shape
        out, store = kinds["mix"](h.reshape(B * T, H), at(kinds["leaves"]), store, ordinal)
        return out.reshape(B, T, H), store

    own = lambda: at(kinds["attention"])
    held = set(flags[group[0]:group[0] + group[1]])
    if held == {1}:
        out, store = state_layer(store)
        return out, ck, cv, store
    if held == {0} and project is None:
        out, ck = attend(h, own(), li, ordinal, ck)
        return out, ck, cv, store
    assert project is not None, "an attention seam that owns the pool stands beside state layers in runs of one kind"
    if held == {0}:
        q, *_ = qkv = project(h, own(), li)
        ck, cv, _ = write(ck, cv, qkv, plane=ordinal)
        return attend(h, own(), li, ordinal, q, ck, cv), ck, cv, store
    is_state = jnp.asarray(flags, bool)[li]
    nothing = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(lambda: project(h, own(), li)))
    qkv = jax.lax.cond(is_state, lambda: nothing, lambda: project(h, own(), li))
    ck, cv, _ = write(ck, cv, qkv, plane=ordinal, skip=is_state)
    out, store = jax.lax.cond(
        is_state, lambda ck, cv, store: state_layer(store),
        lambda ck, cv, store: (attend(h, own(), li, ordinal, qkv[0], ck, cv), store), ck, cv, store)
    return out, ck, cv, store


def init_params(rng: jax.Array, cfg: LlamaConfig, dtype=jnp.bfloat16) -> Params:
    """Random init (normal 0.02). Layers stacked on axis 0."""
    L, H, I, V = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    keys = jax.random.split(rng, 8)

    from inference_gateway_tpu.ops.quant import seeded_std

    def norm(key, shape, leaf=None):
        # 0.02; an EVA model's leaves as the seeded build sizes them (ops/quant._eva_std)
        std = seeded_std(cfg, leaf) if cfg.eva_window and leaf else 0.02
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    # A norm's stored weight for an effective weight of one: 0 where the model adds one to it.
    one = jnp.zeros if cfg.norm_offset else jnp.ones

    params: Params = {
        "embed": norm(keys[0], (V, H), "embed"),
        "layers": {
            "attn_norm": one((L, H), dtype),
            "wq": norm(keys[1], (L, H, Hq * D), "wq"),
            "wk": norm(keys[2], (L, H, Hkv * D), "wk"),
            "wv": norm(keys[3], (L, H, Hkv * D), "wv"),
            "wo": norm(keys[4], (L, Hq * D, H), "wo"),
            "mlp_norm": one((L, H), dtype),
            "wg": norm(keys[5], (L, H, I), "wg"),
            "wu": norm(keys[6], (L, H, I), "wu"),
            "wd": norm(keys[7], (L, I, H), "wd"),
        },
        "final_norm": one((H,), dtype),
    }
    if cfg.qkv_bias:
        params["layers"]["bq"] = jnp.zeros((L, Hq * D), dtype)
        params["layers"]["bk"] = jnp.zeros((L, Hkv * D), dtype)
        params["layers"]["bv"] = jnp.zeros((L, Hkv * D), dtype)
    if cfg.eva_window:
        params["layers"].update(eva_vectors(jax.random.fold_in(rng, 61), cfg, dtype))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm(jax.random.fold_in(rng, 99), (H, V * cfg.num_pred_heads), "lm_head")
    return params


def eva_vectors(rng: jax.Array, cfg: LlamaConfig, dtype) -> Params:
    """EVA's two learned vectors a head and layer, as the model's initialiser draws
    them: a unit normal cut at one. Not scaled down: the pooling weights of a chunk
    must differ, or a mean would pass for the model."""
    shape = (cfg.num_layers, cfg.num_kv_heads, cfg.hd)
    mu, phi = (jnp.clip(jax.random.normal(k, shape, jnp.float32), -1.0, 1.0).astype(dtype)
               for k in jax.random.split(rng))
    return {"eva_mu": mu, "eva_phi": phi}


def further_params(cfg: LlamaConfig) -> int:
    """Parameters beside the dense stack's (``serving/profiles.llama_param_count``): the
    head's further prediction heads and EVA's two vectors a head and layer."""
    heads = 0 if cfg.tie_word_embeddings else (cfg.num_pred_heads - 1) * cfg.vocab_size * cfg.hidden_size
    return heads + (cfg.num_layers * 2 * cfg.num_kv_heads * cfg.hd if cfg.eva_window else 0)


def init_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    """Contiguous KV cache: k/v of shape (L, B, S, Hkv, D)."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _layer(
    x: jnp.ndarray,  # (B, T, H)
    lp: Params,  # this layer's params, leading L axis removed
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    k_cache: jnp.ndarray | None,  # (Slots, S, Hkv, D)
    v_cache: jnp.ndarray | None,
    slot_ids: jnp.ndarray | None,  # (B,) cache rows written by this batch
    scatter_pos: jnp.ndarray | None,  # (B, T) int32 write indices (S = drop)
    attn_impl,  # (q, k, v) -> attn; masking/flash dispatch decided by caller
    cfg: LlamaConfig,
    decode: bool,
):
    B, T, H = x.shape
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd

    h = rms_norm(x, _nw(lp["attn_norm"], cfg), cfg.rms_norm_eps)
    q = qmatmul(h, lp["wq"])
    k = qmatmul(h, lp["wk"])
    v = qmatmul(h, lp["wv"])
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    q = q.reshape(B, T, Hq, D)
    k = k.reshape(B, T, Hkv, D)
    v = v.reshape(B, T, Hkv, D)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    new_k_cache = new_v_cache = None
    if k_cache is not None:
        rows = (jnp.arange(B) if slot_ids is None else slot_ids)[:, None]
        new_k_cache = k_cache.at[rows, scatter_pos].set(k.astype(k_cache.dtype), mode="drop")
        new_v_cache = v_cache.at[rows, scatter_pos].set(v.astype(v_cache.dtype), mode="drop")

    if decode:
        # Attend over cache rows; gather when batch rows map onto slots.
        kc = new_k_cache if slot_ids is None else new_k_cache[slot_ids]
        vc = new_v_cache if slot_ids is None else new_v_cache[slot_ids]
        attn = attn_impl(q, kc.astype(q.dtype), vc.astype(q.dtype))
    else:
        attn = attn_impl(q, k, v)
    x = x + qmatmul(attn.reshape(B, T, Hq * D), lp["wo"])

    h = rms_norm(x, _nw(lp["mlp_norm"], cfg), cfg.rms_norm_eps)
    act = _ACT[cfg.hidden_act]
    x = x + qmatmul(act(qmatmul(h, lp["wg"])) * qmatmul(h, lp["wu"]), lp["wd"])
    return x, new_k_cache, new_v_cache


@partial(jax.jit, static_argnames=("cfg", "mode", "last_only", "ring_mesh"))
def forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # (B, T) int32
    positions: jnp.ndarray,  # (B, T) int32 absolute positions
    lengths: jnp.ndarray,  # (B,) valid length: prefill = prompt len; decode = cache len incl. this token
    cache: Params | None = None,
    mode: str = "prefill",  # "prefill" | "decode"
    last_only: bool = False,
    slot_ids: jnp.ndarray | None = None,  # (B,) cache rows for this batch
    embeds: jnp.ndarray | None = None,  # (B, T, H) overrides embed[tokens] (multimodal)
    ring_mesh=None,  # mesh with sp>1: fresh prefill attends via ring attention
) -> tuple[jnp.ndarray, Params | None]:
    """Run the decoder. Returns (logits, updated_cache).

    prefill: queries attend to this call's keys only (fresh requests);
             cache (if given) is written at ``positions``. ``slot_ids``
             maps batch rows onto cache rows so a small prefill batch can
             write into a large slot cache (continuous batching).
             REQUIRES positions[b] == arange(T) on the single-TPU-chip
             flash path: the Pallas kernel derives absolute query/key
             positions from the row index with offset 0, while the
             einsum path masks by the actual ``positions`` array. The
             engine always passes contiguous-from-zero positions for
             fresh prefill; callers with left-padded or shifted rows
             must use mode="prefill_chunk" (which carries per-row
             ``q_offsets``) or disable flash via IG_TPU_FLASH=0.
    decode:  T must be 1 and the batch must cover every cache row;
             attends to the whole cache masked to ``lengths``.
    prefill_chunk: chunked prefill — this call's tokens are written at
             ``positions`` and queries attend to the WHOLE cache row
             causally (prior chunks + this one); batch rows must align
             with cache rows. ``lengths`` = tokens valid after this
             chunk. Bounds prefill memory to O(chunk × cache).
    """
    if cfg.eva_window:
        raise NotImplementedError(
            "EVA attention is served over the paged pool alone (a window is folded into a page of summaries); "
            "the dense cache has no path for it")
    B, T = tokens.shape
    x = params["embed"][tokens] if embeds is None else embeds.astype(params["embed"].dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    inv_freq = rope_inv_freq(cfg.hd, cfg.rope_theta, cfg.rope_scaling_dict)
    cos, sin = rope_cos_sin(positions, inv_freq)

    if mode == "decode":
        assert cache is not None
        S = cache["k"].shape[2]
        mask = decode_mask(S, lengths)
        if cfg.sliding_window:
            span = jnp.arange(S)
            mask = mask & (span[None, None, :] > lengths[:, None, None] - 1 - cfg.sliding_window)
        scatter_pos = positions
    elif mode == "prefill_chunk":
        assert cache is not None
        S = cache["k"].shape[2]
        span = jnp.arange(S)
        # Key visible iff its cache position is ≤ the query's absolute
        # position and within the row's valid length.
        mask = (span[None, None, :] <= positions[:, :, None]) & (
            span[None, None, :] < lengths[:, None, None]
        )
        if cfg.sliding_window:
            mask = mask & (span[None, None, :] > positions[:, :, None] - cfg.sliding_window)
        valid = positions < lengths[:, None]
        scatter_pos = jnp.where(valid, positions, S)
    else:
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        mask = causal_prefill_mask(positions, lengths)
        if cfg.sliding_window:
            key_pos = positions
            mask = mask & (key_pos[:, None, :] > positions[:, :, None] - cfg.sliding_window)
        if cache is not None:
            S = cache["k"].shape[2]
            scatter_pos = jnp.where(valid, positions, S)  # S = out of bounds -> drop
        else:
            scatter_pos = None

    attend_cache = mode in ("decode", "prefill_chunk")

    # Attention dispatch, decided at trace time: the Pallas flash kernel
    # for prefill shapes on a single TPU chip (fresh prompts AND chunked
    # prefill over the cache row — where long-prompt TTFT is won), the
    # masked einsum elsewhere (CPU, meshes, small buckets).
    from inference_gateway_tpu.ops.flash_attention import flash_prefill_attention, use_flash_prefill

    if mode == "prefill":
        flash_ok = use_flash_prefill(T, T, cfg.hd)
    elif mode == "prefill_chunk":
        flash_ok = use_flash_prefill(T, cache["k"].shape[2], cfg.hd)
    else:
        flash_ok = False

    if mode == "prefill" and ring_mesh is not None:
        # Sequence-parallel exact prefill: q/k/v are seq-sharded over the
        # mesh's sp axis and KV blocks rotate the ring (ops/
        # ring_attention.py). Long-context path — prompts beyond the
        # largest bucket prefill in ONE pass with O(T/sp) memory per
        # device instead of a serial chunk loop (SURVEY.md §2.4 SP row,
        # §5 long-context). Requires positions[b] == arange(T) (fresh
        # prefill) and no sliding window (the engine gates on both).
        from inference_gateway_tpu.ops.ring_attention import make_ring_attention

        assert cfg.sliding_window is None, "ring prefill does not window"
        ring = make_ring_attention(ring_mesh, axis="sp", causal=True)

        def attn_impl(q, k, v):
            return ring(q, k, v, lengths)
    elif mode == "prefill" and flash_ok:
        def attn_impl(q, k, v):
            return flash_prefill_attention(q, k, v, lengths, window=cfg.sliding_window)
    elif mode == "prefill_chunk" and flash_ok:
        def attn_impl(q, kc, vc):
            return flash_prefill_attention(q, kc, vc, lengths, q_offsets=positions[:, 0],
                                           window=cfg.sliding_window)
    else:
        def attn_impl(q, k, v):
            return gqa_attend(q, k, v, mask)

    if cache is not None:
        def body(x, per_layer):
            lp, kc, vc = per_layer
            x, nk, nv = _layer(x, lp, cos, sin, kc, vc, slot_ids, scatter_pos, attn_impl, cfg, attend_cache)
            return x, (nk, nv)

        x, (new_k, new_v) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": new_k, "v": new_v}
    else:
        def body(x, lp):
            x, _, _ = _layer(x, lp, cos, sin, None, None, None, None, attn_impl, cfg, attend_cache)
            return x, None

        x, _ = jax.lax.scan(body, x, params["layers"])
        new_cache = None

    x = rms_norm(x, _nw(params["final_norm"], cfg), cfg.rms_norm_eps)
    if last_only:
        if mode == "decode":
            idx = jnp.zeros_like(lengths)
        else:
            # Local index of each row's last valid token: chunks start at
            # positions[:, 0] (0 for fresh prefill).
            idx = jnp.maximum(lengths - 1 - positions[:, 0], 0)
        x = x[jnp.arange(B), idx]  # (B, H)
    if cfg.tie_word_embeddings:
        logits = (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)
    else:
        logits = qmatmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache


def loss_fn(params: Params, cfg: LlamaConfig, tokens: jnp.ndarray, targets: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross-entropy over valid positions (training path used by
    the multi-chip dry run)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    logits, _ = forward(params, cfg, tokens, positions, lengths, mode="prefill")
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    valid = (jnp.arange(T)[None, :] < lengths[:, None]).astype(jnp.float32)
    return (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)


# ---------------------------------------------------------------------------
# Paged-cache forward (serving fast path)
# ---------------------------------------------------------------------------

def _dense_ffn(x: jnp.ndarray, attn_in: jnp.ndarray, lp: Params, li, valid, cfg: LlamaConfig) -> jnp.ndarray:
    """Norm + gated MLP residual contribution (the non-MoE FFN block).
    Of the seam's arguments (below) it reads ``x`` and its own leaves."""
    del attn_in, li, valid
    h = rms_norm(x, _nw(lp["mlp_norm"], cfg), cfg.rms_norm_eps)
    act = _ACT[cfg.hidden_act]
    return qmatmul(act(qmatmul(h, lp["wg"])) * qmatmul(h, lp["wu"]), lp["wd"])


def _eva_fold(params: Params, cfg: LlamaConfig, cache: Params, page_table, first_pos, live, rows, contexts, queries):
    """EVA's part of a step before its layers (ISSUE 61): the window behind every
    row that starts one is folded into its page of summaries, in every layer's
    plane, BEFORE the step writes (ops/eva_attention.fold). The layers then read
    the pool as any dense model's: the lengths handed in are cached rows. Returns
    (the cache, float32 (5,): ``EVA_STATS``). ``rows`` (R,) the rows a row of the step
    holds after it, ``contexts`` its tokens, ``queries`` how many of them are new."""
    from inference_gateway_tpu.ops.eva_attention import fold

    assert cfg.num_heads == cfg.num_kv_heads, "EVA's vectors are a head's: no grouped heads"
    with jax.named_scope("attention"):
        cache, rolls = fold(cache, page_table, first_pos, live, params["layers"]["eva_mu"],
                            params["layers"]["eva_phi"], window=cfg.eva_window, chunk=cfg.eva_chunk)
    with jax.named_scope("carry"):
        pairs = queries * rows - queries * (queries - 1) // 2   # a chunk's queries see one row more each
        stats = jnp.stack([jnp.sum(jnp.where(live, rows, 0)), jnp.sum(jnp.where(live, contexts, 0)),
                           rolls, jnp.sum(jnp.where(live, pairs, 0)),
                           jnp.sum(jnp.where(live, queries, 0))]).astype(jnp.float32)
    return cache, stats


@partial(jax.jit, static_argnames=("cfg", "mode", "last_only", "mesh", "ring_mesh", "with_aux"))
def forward_paged(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # (B, T)
    positions: jnp.ndarray,  # (B, T)
    lengths: jnp.ndarray,  # (B,)
    cache: Params,  # {"k","v"}: (L, P, page_size, Hkv*D)
    write_idx: jnp.ndarray,  # (B, T) flat page*page_size+offset positions (OOB = drop)
    page_table: jnp.ndarray,  # (B, max_pages)
    mode: str = "prefill",
    last_only: bool = True,
    mesh=None,  # tp mesh: decode runs the shard_mapped Pallas kernel
    ring_mesh=None,  # mesh with sp>1: fresh prefill attends via ring attention
    valid: jnp.ndarray | None = None,  # (B,) decode rows that carry a stream: the counted call's contract (a done row's length is 0, which says the same)
    with_aux: bool = False,  # a third result: what the model counts (``Family.chunk_stats``)
) -> tuple[jnp.ndarray, Params]:
    """Like ``forward`` but against the paged KV cache
    (serving/kv_cache.py). Decode attention runs the Pallas ragged
    paged-attention kernel (ops/paged_attention.py). ``prefill_chunk``
    attends causally over the slot's gathered pages — the prefix-cache
    path: shared prefix pages are already populated, only the tail is
    computed here.

    An EVA model (``cfg.eva_window``): ``lengths`` and ``write_idx`` are in the
    pool's rows (``cached_rows``), ``positions`` the tokens' own, for the rotation
    and for the roll; ``mode="decode"`` alone (prompts go through the mixed step)."""
    stats = None
    if cfg.eva_window:
        if mode != "decode":
            raise NotImplementedError(
                f"EVA attention admits prompts through the mixed step alone; forward_paged mode {mode!r} has no path for it")
        with jax.named_scope("carry"):
            rows = (positions[:, 0], lengths > 0, lengths, positions[:, 0] + 1, jnp.ones_like(lengths))
        cache, stats = _eva_fold(params, cfg, cache, page_table, *rows)
    out = forward_paged_impl(params, cfg, tokens, positions, lengths, cache,
                             write_idx, page_table, mode, last_only, mesh,
                             partial(_dense_ffn, cfg=cfg), ring_mesh=ring_mesh)
    return (*out, stats) if with_aux else out


def _scan_layers(params: Params, unscanned: tuple) -> Params:
    """The leaves a layer scan slices. An FFN that reads a stacked leaf
    whole, by layer index (the expert stacks: ops/moe.grouped_matmul),
    names it in ``unscanned`` so that no per-layer slice of it exists."""
    return {k: v for k, v in params["layers"].items() if k not in unscanned}


def forward_paged_impl(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    lengths: jnp.ndarray,
    cache: Params,
    write_idx: jnp.ndarray,
    page_table: jnp.ndarray,
    mode: str,
    last_only: bool,
    mesh,
    ffn,  # the FFN seam, below; MoE plugs in here
    ring_mesh=None,
    unscanned: tuple = (),
    valid: jnp.ndarray | None = None,  # (B,) decode rows that carry a stream
    with_aux: bool = False,
    mixer=None,  # the attention seam, below; a learned sparse attention plugs in here
    prefix=None,  # leading layers with an FFN of their own (_layer_groups), or None
    kinds=None,  # a per-layer kind with a per-kind cache, below; a layer that owns no pages plugs in here
    residual=None,  # the residual seam, below; more than one stream a token plugs in here
):
    """Shared paged-decoder skeleton: attention + cache paging are
    family-independent; the FFN block (dense gated MLP vs MoE) is the
    ``ffn`` callable (models/mixtral.py reuses this for paged MoE
    serving).

    The attention seam (ISSUE 36): ``mixer`` is None for every model whose
    attention is the kernels' (nothing below changes for them), or a dict
    ``{"cache": names, "decode": fn, "ragged": fn}``. ``cache`` names the
    arrays the model keeps beside ``k`` and ``v`` under the same page ids,
    (L, P, ...) each, a page's layout the model's own; the layer loop
    carries them as it carries the pages, by page: (L * P, ...). ``decode(h, q, lp, li, positions, pools,
    w_idx, table, lengths, page_size=)`` (here) and ``ragged(h, q, lp, li,
    positions, pools, w_idx, table, q_starts, q_lens, kv_lens, page_size=)``
    (forward_ragged_impl)
    get the layer's normed input, its queries after norm and rotary, the
    flat pools ``{"k", "v", *cache}`` with this layer's keys and values
    already written, the flat write indices and the layer's page table;
    they write their own arrays, attend, and return ``(attention, the
    pools they wrote {name: array}, a per-layer auxiliary)``. With a mixer
    a layer's auxiliary is the pair (the FFN's, the mixer's).

    A mixer that OWNS the pool (ISSUE 47, a latent attention): the dict also
    has ``"pool": name``; the cache is then ``{name: (L, P, page_size, W)}``
    alone, no ``k`` and no ``v``, and the skeleton projects nothing.
    ``decode(h, lp, li, positions, pool, w_idx, table, lengths, page_size=)``
    and ``ragged(h, lp, li, positions, pool, w_idx, table, q_starts, q_lens,
    kv_lens, page_size=)`` get the layer's normed input and the flat pool
    (L * P * page_size, W); they project, write their rows at ``w_idx``,
    attend, and return ``(the heads' outputs, folded, for ``wo``; the pool)``.
    It has no auxiliary, so it may stand behind a ``prefix``, and beside a
    per-layer kind whose layers are grouped by runs (ISSUE 54: it is then the
    OTHER kind of layer, its leaves ``kinds["attention"]`` and the pool's
    leading axis the layers of its kind alone: ``_mix_by_kind``).

    The residual seam (ISSUE 47): ``residual`` is None for every model with
    ONE stream of ``hidden_size`` (``x + y``; nothing below changes for them),
    or a dict ``{"enter": fn, "read": fn, "write": fn, "exit": fn}`` for one
    whose layer loop carries several streams a token (in whatever layout the
    seam chooses: the loop only carries it): ``enter(x)`` makes them of the
    embedding, ``read(X, lp, block) -> (u, maps)`` is what block
    ``"attn"`` or ``"ffn"`` of a layer reads (its own input norm follows),
    ``write(X, y, maps)`` the stream after the block's output ``y``,
    ``exit(X)`` what the final norm reads.

    The per-layer kind (ISSUE 43): ``kinds`` is None for every model whose
    layers all write keys (nothing below changes for them), or a dict
    ``{"state": flags, "cache": names, "attention": leaves, "leaves": leaves,
    "mix": fn}`` (and ``"runs": True``: ``_layer_groups``). ``state`` is one
    0/1 flag a layer: 1 a layer whose token mixer owns no pages and keeps, a
    slot, its state in the arrays ``cache[name]`` (state layers, slots, ...)
    of ``names``, which the layer loop carries beside the flat pools.
    The pool's leading axis is then the CACHING layers alone, and the
    operators' leaves are stacks of their own, ``attention`` (caching layers,
    ...) under the attention's names and ``leaves`` (state layers, ...): a
    layer's plane, store entry and leaves are read at its ordinal among its
    kind (``_mix_by_kind``). ``mix(h (N, H), a state layer's leaves, the store
    {name: array}, the ordinal) -> (the residual contribution (N, H), the
    store)``: the mixer reads and writes its own entry (one with a kernel
    does so in place); the
    skeletons' scanned leaves keep ``attn_norm``, the operator's input norm.

    The seam: ``ffn(x, attn_in, lp, li, valid)`` returns the block's
    residual contribution, or that and a per-layer auxiliary array.
    ``x`` is the stream after attention, ``attn_in`` the attention's
    normed input (a router placed before attention reads it), ``lp`` the
    layer's scanned leaves, ``li`` its index, ``valid`` (B*T,) which
    tokens are real. With ``with_aux`` the auxiliaries come back stacked
    over layers as a third result (those of the layers behind a ``prefix``:
    its own FFN returns none)."""
    from inference_gateway_tpu.ops.flash_attention import flash_prefill_attention, use_flash_prefill
    from inference_gateway_tpu.ops.paged_attention import paged_attention

    B, T = tokens.shape
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    own = _owns_pool(mixer)
    L, P, page_size, HkvD = cache[mixer["pool"] if own else "k"].shape
    flat = P * page_size

    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens)
        if residual is not None:
            x = residual["enter"](x)

    def windowed(mask, key_pos, window):
        if not window:
            return mask
        return mask & (key_pos > positions[:, :, None] - window)

    with jax.named_scope("carry"):  # what the step derives from its rows' positions and lengths, once for every layer
        inv_freq = rope_inv_freq(cfg.hd, cfg.rope_theta, cfg.rope_scaling_dict)
        cos, sin = rope_cos_sin(positions, inv_freq)
        if mode == "prefill":
            # Keys are this call's tokens at absolute `positions`
            # (same windowing as the dense path, forward() above).
            prefill_mask = causal_prefill_mask(positions, lengths)
            tok_valid = jnp.arange(T)[None, :] < lengths[:, None]
        elif mode == "prefill_chunk":
            S_gather = page_table.shape[1] * page_size
            key_pos = jnp.arange(S_gather)
            chunk_mask = (key_pos[None, None, :] <= positions[:, :, None]) & (
                key_pos[None, None, :] < lengths[:, None, None]
            )
            tok_valid = positions < lengths[:, None]
        else:
            tok_valid = None if valid is None else jnp.broadcast_to(valid[:, None], (B, T))
        if tok_valid is not None:
            tok_valid = tok_valid.reshape(B * T)
    decode = mode == "decode"

    # The layer loop CARRIES the cache as one flat buffer instead of
    # streaming per-layer planes through scan xs/ys. Stacked ys rebuild
    # the whole (L, P, page_size, HkvD) array every call — inside the
    # fused decode scan that was a full-cache read+write per token
    # (~3.6 ms/step at TinyLlama pool sizes on v5e, measured round 3).
    # As a carry, the scatter lowers to an in-place update of just the
    # written rows, and attention reads pages straight out of the big
    # buffer via layer-offset page indices — no per-layer slice is ever
    # materialized. Layout: flat row (li * P + p) holds layer li's copy
    # of logical page p; reshapes to/from the at-rest (L, P, ...) shape
    # are metadata-only.
    total = L * flat

    assert mixer is None or kinds is None or own, "an attention seam beside a per-layer kind: only one that owns the pool"
    extra = tuple(kinds["cache"]) if kinds is not None else tuple(mixer["cache"]) if mixer is not None else ()
    if (mixer is not None or kinds is not None) and mode != "decode":
        raise NotImplementedError(
            f"a model with an attention seam or a state store admits through the mixed step; forward_paged mode {mode!r} has no path for it")

    assert mixer is None or own or prefix is None, "an attention seam's counts of a prefix's layers would be lost"

    project = partial(_project_qkv, cfg=cfg, cos=cos, sin=sin, cache=cache)
    write = partial(_write_kv, write_idx=write_idx, flat=flat, total=total, mesh=mesh)

    def attend(h, ap, li, plane, q, ck, cv):
        """The paged kernel over ``plane`` and the output projection."""
        pages_k = ck.reshape(L * P, page_size, HkvD)
        pages_v = cv.reshape(L * P, page_size, HkvD)
        layer_table = page_table + plane * P  # (B, max_pages) into the big pool
        attn = paged_attention(q[:, 0], pages_k, pages_v, layer_table, lengths, Hkv,
                               mesh=mesh, **_window_operand(cfg, li))[:, None]  # (B, 1, Hq, D)
        return _attn_out(attn.reshape(B, T, Hq * D), h, ap, cfg)

    def owned(h, ap, li, plane, ck):
        """A seam that owns the pool: its projection, write and read, and ``wo``."""
        attn, ck = mixer["decode"](h[:, 0], ap, li, positions[:, 0], ck,
                                   _flat_index(write_idx[:, 0], plane, flat, total), page_table + plane * P,
                                   lengths, page_size=page_size)
        return _attn_out(attn.reshape(B, T, -1), h, ap, cfg), ck

    def attention(h, ap, li, plane, ck, cv, ce):
        """The attention operator on a layer's normed input: ``ap`` holds its
        leaves, ``plane`` is its plane of the pool. Returns (the residual
        contribution, the pools, the attention seam's auxiliary or None)."""
        if own:
            out, ck = owned(h, ap, li, plane, ck)
            return out, ck, cv, ce, None
        q, k, v, *_ = qkv = project(h, ap, li)
        ck, cv, w_idx = write(ck, cv, qkv, plane=plane)
        if mixer is None and decode:
            return attend(h, ap, li, plane, q, ck, cv), ck, cv, ce, None
        pages_k = ck.reshape(L * P, page_size, HkvD)
        pages_v = cv.reshape(L * P, page_size, HkvD)
        layer_table = page_table + plane * P  # (B, max_pages) into the big pool

        def attend_prompt(window):
            if mode == "prefill_chunk":
                # Gather the slot's pages (prefix + just-written tail) and
                # attend causally by absolute position.
                kg = pages_k[layer_table].reshape(B, -1, Hkv, D).astype(q.dtype)
                vg = pages_v[layer_table].reshape(B, -1, Hkv, D).astype(q.dtype)
                if use_flash_prefill(T, kg.shape[1], D):
                    return flash_prefill_attention(q, kg, vg, lengths, q_offsets=positions[:, 0],
                                                   window=window)
                return gqa_attend(q, kg, vg, windowed(chunk_mask, key_pos[None, None, :], window))
            if ring_mesh is not None:
                # Fresh long-prompt prefill over the sp ring; pages were
                # just written above, attention runs on this call's k/v.
                from inference_gateway_tpu.ops.ring_attention import make_ring_attention

                return make_ring_attention(ring_mesh, axis="sp", causal=True)(q, k, v, lengths)
            if use_flash_prefill(T, T, D):
                return flash_prefill_attention(q, k, v, lengths, window=window)
            return gqa_attend(q, k, v, windowed(prefill_mask, positions[:, None, :], window))

        mix_aux = None
        if mixer is None:
            attn = _attend_by_window(cfg, li, attend_prompt)
        else:
            attn, wrote, mix_aux = mixer["decode"](
                h[:, 0], q[:, 0], ap, li, positions[:, 0],
                {"k": ck, "v": cv, **dict(zip(extra, ce))}, w_idx[:, 0], layer_table, lengths,
                page_size=page_size)
            ce = [wrote.get(n, a) for n, a in zip(extra, ce)]
            attn = attn[:, None]
        return _attn_out(attn.reshape(B, T, Hq * D), h, ap, cfg), ck, cv, ce, mix_aux

    def body(carry, per_layer, ffn, group):
        x, ck, cv, *ce = carry  # ck/cv: (L*P*page_size, HkvD) flat carry
        lp, li = per_layer
        with jax.named_scope("attention"):
            u, maps = _block_in(residual, x, lp, "attn")
            h = rms_norm(u, _nw(lp["attn_norm"], cfg), cfg.rms_norm_eps)
            if kinds is None:
                out, ck, cv, ce, mix_aux = attention(h, lp, li, li, ck, cv, ce)
            else:
                out, ck, cv, store = _mix_by_kind(kinds, group, li, h, ck, cv, dict(zip(extra, ce)),
                                                  (None, None, owned) if own else (project, write, attend))
                ce = [store[n] for n in extra]
            x = _block_out(residual, x, _scaled(out, cfg), maps)
        with jax.named_scope("mlp"):
            u, maps = _block_in(residual, x, lp, "ffn")
            y = ffn(u, h, lp, li, tok_valid)
            y, aux = y if isinstance(y, tuple) else (y, None)
            x = _block_out(residual, x, _scaled(_ffn_out(y, lp, cfg), cfg), maps)
        return (x, ck, cv, *ce), (aux if mixer is None or own else (aux, mix_aux))

    ck0 = cache[mixer["pool"] if own else "k"].reshape(total, HkvD)
    cv0 = None if own else cache["v"].reshape(total, HkvD)  # (None: an empty leaf of the carry)
    # An attention seam's arrays are carried by page, a state store as it lies.
    carry = (x, ck0, cv0, *(cache[n] if kinds is not None else cache[n].reshape((L * P,) + cache[n].shape[2:])
                            for n in extra))
    for leaves, group_ffn, first, count in _layer_groups(params, ffn, unscanned, prefix, cfg.num_layers, kinds):
        carry, aux = jax.lax.scan(partial(body, ffn=group_ffn, group=(first, count)), carry,
                                  (leaves, jnp.arange(first, first + count)))
    x, ck, cv, *ce = carry
    if own:
        new_cache = {mixer["pool"]: ck.reshape(L, P, page_size, HkvD),
                     **({n: a for n, a in zip(extra, ce)} if kinds is not None else {})}
    else:
        new_cache = {"k": ck.reshape(L, P, page_size, HkvD),
                     "v": cv.reshape(L, P, page_size, HkvD),
                     **{n: a.reshape(cache[n].shape) for n, a in zip(extra, ce)}}

    with jax.named_scope("head"):
        if residual is not None:
            x = residual["exit"](x)
        x = rms_norm(x, _nw(params["final_norm"], cfg), cfg.rms_norm_eps)
        if last_only:
            if mode == "decode":
                idx = jnp.zeros_like(lengths)
            else:  # prefill starts at 0; prefill_chunk at positions[:, 0]
                idx = jnp.maximum(lengths - 1 - positions[:, 0], 0)
            x = x[jnp.arange(B), idx]
        logits = _head(params, cfg, x)
    return (logits, new_cache, aux) if with_aux else (logits, new_cache)


# ---------------------------------------------------------------------------
# Ragged mixed-batch forward (ISSUE 12): prefill chunks + decode rows in
# one program over the paged cache.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "mesh", "with_aux"))
def forward_ragged(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # (1, T) packed token ids, rows back to back
    positions: jnp.ndarray,  # (1, T) absolute positions
    cache: Params,  # {"k","v"}: (L, P, page_size, Hkv*D)
    write_idx: jnp.ndarray,  # (1, T) flat page*page_size+offset (OOB = drop)
    page_table: jnp.ndarray,  # (R, max_pages) row-aligned
    q_starts: jnp.ndarray,  # (R,) packed offset of row r's queries
    q_lens: jnp.ndarray,  # (R,) query count (0 = inactive row)
    kv_lens: jnp.ndarray,  # (R,) total kv length after this step
    mesh=None,
    with_aux: bool = False,  # a third result: what the model counts (``Family.mixed_stats``)
) -> tuple[jnp.ndarray, Params]:
    """One MIXED engine step over the paged cache: the packed token axis
    carries every row's new tokens (a decode row contributes its pending
    token, a prefill row its whole chunk), per-row descriptors say which
    span belongs to which slot, and attention is the ragged paged op
    (ops/paged_attention.ragged_paged_attention) — ONE launch per layer
    for the whole batch, whatever mix of prefill and decode it holds.
    Returns per-ROW last-position logits (R, V) and the updated cache.

    This replaces the bucketed ``_prefill_fn`` family for
    paged serving: one compiled program at one static packed width
    instead of one program per prompt bucket, and no bucket padding —
    only the packed tail beyond the live tokens is dead work.

    An EVA model (``cfg.eva_window``): ``kv_lens`` and ``write_idx`` are in the
    pool's rows, and no row's chunk straddles a window's end (the scheduler cuts
    it there), so a chunk's rows lie back to back as its tokens do."""
    stats = None
    if cfg.eva_window:
        with jax.named_scope("carry"):
            T = tokens.shape[1]
            first = positions[0, jnp.clip(q_starts, 0, T - 1)]
            last = positions[0, jnp.clip(q_starts + q_lens - 1, 0, T - 1)]
            rows = (first, q_lens > 0, kv_lens, last + 1, q_lens)
        cache, stats = _eva_fold(params, cfg, cache, page_table, *rows)
    out = forward_ragged_impl(params, cfg, tokens, positions, cache, write_idx,
                              page_table, q_starts, q_lens, kv_lens, mesh,
                              partial(_dense_ffn, cfg=cfg))
    return (*out, stats) if with_aux else out


def forward_ragged_impl(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Params,
    write_idx: jnp.ndarray,
    page_table: jnp.ndarray,
    q_starts: jnp.ndarray,
    q_lens: jnp.ndarray,
    kv_lens: jnp.ndarray,
    mesh,
    ffn,  # the FFN seam of forward_paged_impl (MoE plugs in here)
    unscanned: tuple = (),
    with_aux: bool = False,
    mixer=None,  # the attention seam of forward_paged_impl
    prefix=None,  # the leading group of forward_paged_impl
    kinds=None,  # the per-layer kind of forward_paged_impl
    residual=None,  # the residual seam of forward_paged_impl
    hidden: bool = False,  # the last block's stream of EVERY packed token in place of the rows' logits
    tok_valid=None,  # (T,) which packed tokens are real, where the rows are not packed back to back from 0
):
    """Shared ragged skeleton, same flat-carry cache discipline as
    forward_paged_impl (the scatter lowers to an in-place row update;
    attention reads pages straight out of the big buffer).

    ``hidden`` (ISSUE 56): the first result is the stream behind the last block,
    (T, hidden_size), BEFORE the final norm: a model that drafts with its own
    next-token module reads it there, and takes logits at the queries it
    chooses. The pool may hold more planes than the layers walked here (the
    module's own, behind them)."""
    from inference_gateway_tpu.ops.paged_attention import ragged_paged_attention

    B, T = tokens.shape  # B == 1: the packed axis IS the batch
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    own = _owns_pool(mixer)
    L, P, page_size, HkvD = cache[mixer["pool"] if own else "k"].shape
    flat = P * page_size
    total = L * flat

    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens)
        if residual is not None:
            x = residual["enter"](x)
    with jax.named_scope("carry"):
        inv_freq = rope_inv_freq(cfg.hd, cfg.rope_theta, cfg.rope_scaling_dict)
        cos, sin = rope_cos_sin(positions, inv_freq)
        # Rows are packed back to back from 0: the tail past them is padding.
        if tok_valid is None:
            tok_valid = jnp.arange(T) < jnp.sum(q_lens)

    assert mixer is None or kinds is None or own, "an attention seam beside a per-layer kind: only one that owns the pool"
    extra = tuple(kinds["cache"]) if kinds is not None else tuple(mixer["cache"]) if mixer is not None else ()
    assert mixer is None or own or prefix is None, "an attention seam's counts of a prefix's layers would be lost"

    project = partial(_project_qkv, cfg=cfg, cos=cos, sin=sin, cache=cache)
    write = partial(_write_kv, write_idx=write_idx, flat=flat, total=total, mesh=mesh)

    def attend(h, ap, li, plane, q, ck, cv):
        pages_k = ck.reshape(L * P, page_size, HkvD)
        pages_v = cv.reshape(L * P, page_size, HkvD)
        layer_table = page_table + plane * P
        attn = ragged_paged_attention(
            q[0], pages_k, pages_v, layer_table, q_starts, q_lens, kv_lens,
            Hkv, mesh=mesh, **_window_operand(cfg, li))[None]  # (1, T, Hq, D)
        return _attn_out(attn.reshape(B, T, Hq * D), h, ap, cfg)

    def owned(h, ap, li, plane, ck):
        attn, ck = mixer["ragged"](h[0], ap, li, positions[0], ck,
                                   _flat_index(write_idx[0], plane, flat, total), page_table + plane * P,
                                   q_starts, q_lens, kv_lens, page_size=page_size)
        return _attn_out(attn.reshape(B, T, -1), h, ap, cfg), ck

    def attention(h, ap, li, plane, ck, cv, ce):
        if own:
            out, ck = owned(h, ap, li, plane, ck)
            return out, ck, cv, ce, None
        q, *_ = qkv = project(h, ap, li)
        ck, cv, w_idx = write(ck, cv, qkv, plane=plane)
        if mixer is None:
            return attend(h, ap, li, plane, q, ck, cv), ck, cv, ce, None
        attn, wrote, mix_aux = mixer["ragged"](
            h[0], q[0], ap, li, positions[0],
            {"k": ck, "v": cv, **dict(zip(extra, ce))}, w_idx[0], page_table + plane * P,
            q_starts, q_lens, kv_lens, page_size=page_size)
        ce = [wrote.get(n, a) for n, a in zip(extra, ce)]
        return _attn_out(attn[None].reshape(B, T, Hq * D), h, ap, cfg), ck, cv, ce, mix_aux

    def body(carry, per_layer, ffn, group):
        x, ck, cv, *ce = carry
        lp, li = per_layer
        with jax.named_scope("attention"):
            u, maps = _block_in(residual, x, lp, "attn")
            h = rms_norm(u, _nw(lp["attn_norm"], cfg), cfg.rms_norm_eps)
            if kinds is None:
                out, ck, cv, ce, mix_aux = attention(h, lp, li, li, ck, cv, ce)
            else:
                out, ck, cv, store = _mix_by_kind(kinds, group, li, h, ck, cv, dict(zip(extra, ce)),
                                                  (None, None, owned) if own else (project, write, attend))
                ce = [store[n] for n in extra]
            x = _block_out(residual, x, _scaled(out, cfg), maps)
        with jax.named_scope("mlp"):
            u, maps = _block_in(residual, x, lp, "ffn")
            y = ffn(u, h, lp, li, tok_valid)
            y, aux = y if isinstance(y, tuple) else (y, None)
            x = _block_out(residual, x, _scaled(_ffn_out(y, lp, cfg), cfg), maps)
        return (x, ck, cv, *ce), (aux if mixer is None or own else (aux, mix_aux))

    ck0 = cache[mixer["pool"] if own else "k"].reshape(total, HkvD)
    cv0 = None if own else cache["v"].reshape(total, HkvD)  # (None: an empty leaf of the carry)
    carry = (x, ck0, cv0, *(cache[n] if kinds is not None else cache[n].reshape((L * P,) + cache[n].shape[2:])
                            for n in extra))
    for leaves, group_ffn, first, count in _layer_groups(params, ffn, unscanned, prefix, cfg.num_layers, kinds):
        carry, aux = jax.lax.scan(partial(body, ffn=group_ffn, group=(first, count)), carry,
                                  (leaves, jnp.arange(first, first + count)))
    x, ck, cv, *ce = carry
    if own:
        new_cache = {mixer["pool"]: ck.reshape(L, P, page_size, HkvD),
                     **({n: a for n, a in zip(extra, ce)} if kinds is not None else {})}
    else:
        new_cache = {"k": ck.reshape(L, P, page_size, HkvD),
                     "v": cv.reshape(L, P, page_size, HkvD),
                     **{n: a.reshape(cache[n].shape) for n, a in zip(extra, ce)}}

    if hidden:
        assert residual is None, "the stream handed out is the one stream of hidden_size"
        return (x[0], new_cache, aux) if with_aux else (x[0], new_cache)
    with jax.named_scope("head"):
        if residual is not None:
            x = residual["exit"](x)
        x = rms_norm(x, _nw(params["final_norm"], cfg), cfg.rms_norm_eps)
        # Per-ROW logits at each row's last packed query (inactive rows are
        # clamped to index 0; the caller ignores them).
        last = jnp.clip(q_starts + q_lens - 1, 0, T - 1)
        x = x[0, last]  # (R, H)
        logits = _head(params, cfg, x)
    return (logits, new_cache, aux) if with_aux else (logits, new_cache)


# ---------------------------------------------------------------------------
# Presets
# ---------------------------------------------------------------------------

PRESETS: dict[str, LlamaConfig] = {
    "test-tiny": LlamaConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        intermediate_size=128, max_position_embeddings=512,
    ),
    "tinyllama-1.1b": LlamaConfig(
        vocab_size=32000, hidden_size=2048, num_layers=22, num_heads=32, num_kv_heads=4,
        intermediate_size=5632, max_position_embeddings=2048,
    ),
    # Draft for speculative decoding against 32k-vocab llama targets
    # (TinyLlama/Llama-2): ~8x fewer FLOPs per token than TinyLlama.
    "llama-draft-150m": LlamaConfig(
        vocab_size=32000, hidden_size=512, num_layers=4, num_heads=8, num_kv_heads=2,
        intermediate_size=1408, max_position_embeddings=2048,
    ),
    "llama-2-7b": LlamaConfig(
        vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=32,
        intermediate_size=11008, max_position_embeddings=4096,
    ),
    "llama-3-8b": LlamaConfig(
        vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8,
        intermediate_size=14336, rope_theta=500000.0, max_position_embeddings=8192,
    ),
    "llama-3.1-8b": LlamaConfig(
        vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8,
        intermediate_size=14336, rope_theta=500000.0, max_position_embeddings=131072,
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0, "original_max_position_embeddings": 8192,
        },
    ),
    "mistral-7b": LlamaConfig(
        vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8,
        intermediate_size=14336, max_position_embeddings=32768, sliding_window=4096,
    ),
    "gemma-test-tiny": LlamaConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=1,
        intermediate_size=128, head_dim=16, max_position_embeddings=512,
        tie_word_embeddings=True, hidden_act="gelu_tanh", norm_offset=True,
        embed_scale=True, rms_norm_eps=1e-6,
    ),
    "gemma-2b": LlamaConfig(
        vocab_size=256000, hidden_size=2048, num_layers=18, num_heads=8, num_kv_heads=1,
        intermediate_size=16384, head_dim=256, max_position_embeddings=8192,
        tie_word_embeddings=True, hidden_act="gelu_tanh", norm_offset=True,
        embed_scale=True, rms_norm_eps=1e-6,
    ),
    "gemma-7b": LlamaConfig(
        vocab_size=256000, hidden_size=3072, num_layers=28, num_heads=16, num_kv_heads=16,
        intermediate_size=24576, head_dim=256, max_position_embeddings=8192,
        tie_word_embeddings=True, hidden_act="gelu_tanh", norm_offset=True,
        embed_scale=True, rms_norm_eps=1e-6,
    ),
    "qwen2-test-tiny": LlamaConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        intermediate_size=128, max_position_embeddings=512, qkv_bias=True,
        tie_word_embeddings=True,
    ),
    "qwen2.5-7b": LlamaConfig(
        vocab_size=152064, hidden_size=3584, num_layers=28, num_heads=28, num_kv_heads=4,
        intermediate_size=18944, rope_theta=1000000.0, max_position_embeddings=32768,
        qkv_bias=True,
    ),
    "qwen2.5-0.5b": LlamaConfig(
        vocab_size=151936, hidden_size=896, num_layers=24, num_heads=14, num_kv_heads=2,
        intermediate_size=4864, rope_theta=1000000.0, max_position_embeddings=32768,
        qkv_bias=True, tie_word_embeddings=True,
    ),
    # EvaByte-6.5B (ISSUE 61; https://huggingface.co/EvaByte/EvaByte): a byte-level model,
    # 256 bytes + 64 special ids, EVA attention in every layer, no grouped heads. Of its 8
    # prediction heads the first alone is held (multi-byte self-speculation waits).
    "evabyte-6.5b": LlamaConfig(
        vocab_size=320, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=32,
        intermediate_size=11008, rope_theta=100000.0, max_position_embeddings=32768,
        norm_offset=True, eva_window=2048, eva_chunk=16,
    ),
    "evabyte-test-tiny": LlamaConfig(
        vocab_size=320, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=4,
        intermediate_size=128, rope_theta=100000.0, max_position_embeddings=512,
        norm_offset=True, eva_window=32, eva_chunk=4, num_pred_heads=2,
    ),
    "llama-3-70b": LlamaConfig(
        vocab_size=128256, hidden_size=8192, num_layers=80, num_heads=64, num_kv_heads=8,
        intermediate_size=28672, rope_theta=500000.0, max_position_embeddings=8192,
    ),
}


def forward_pipelined(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # (B, T)
    positions: jnp.ndarray,  # (B, T)
    lengths: jnp.ndarray,  # (B,)
    mesh,
    microbatches: int = 4,
    last_only: bool = True,
) -> jnp.ndarray:
    """Pipeline-parallel prefill over the mesh's ``pp`` axis
    (parallel/pipeline.py — SURVEY §2.4 PP row): the stacked layer
    pytree is sharded by stage, B is split into microbatches, and
    activations stream through the GPipe schedule. Embed and the
    lm_head run replicated outside the pipeline (they're the first/last
    "stage 0"/"stage N" work and tiny next to the layer stack). No KV
    cache: PP targets prefill/batch-scoring throughput where
    microbatching hides the bubble; decode stays tp-sharded
    (latency-bound, SURVEY §7)."""
    from inference_gateway_tpu.ops.attention import causal_prefill_mask, gqa_attend
    from inference_gateway_tpu.parallel.pipeline import pipeline_apply

    B, T = tokens.shape
    M = microbatches
    assert B % M == 0, "batch must split into microbatches"
    Bm = B // M

    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)

    payload = {
        "x": x.reshape(M, Bm, T, -1),
        "positions": positions.reshape(M, Bm, T),
        "lengths": lengths.reshape(M, Bm),
    }

    inv_freq = rope_inv_freq(cfg.hd, cfg.rope_theta, cfg.rope_scaling_dict)

    def stage_fn(layers_local, p):
        # Per-row context rebuilt locally from the (small) streamed
        # positions/lengths instead of permuting (B, T, T) masks.
        cos, sin = rope_cos_sin(p["positions"], inv_freq)
        mask = causal_prefill_mask(p["positions"], p["lengths"])
        if cfg.sliding_window:
            mask = mask & (p["positions"][:, None, :] >
                           p["positions"][:, :, None] - cfg.sliding_window)

        def body(x, lp):
            x, _, _ = _layer(x, lp, cos, sin, None, None, None, None,
                             lambda q, k, v: gqa_attend(q, k, v, mask), cfg, False)
            return x, None

        x, _ = jax.lax.scan(body, p["x"], layers_local)
        return {"x": x, "positions": p["positions"], "lengths": p["lengths"]}

    out = pipeline_apply(mesh, stage_fn, params["layers"], payload)
    x = out["x"].reshape(B, T, -1)

    x = rms_norm(x, _nw(params["final_norm"], cfg), cfg.rms_norm_eps)
    if last_only:
        idx = jnp.maximum(lengths - 1 - positions[:, 0], 0)
        x = x[jnp.arange(B), idx]
    if cfg.tie_word_embeddings:
        return (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)
    return qmatmul(x, params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Pipeline-parallel SERVING forward: stage-sharded layers AND KV cache.
# ---------------------------------------------------------------------------
def _layer_tp(x, lp, cos, sin, k_cache, v_cache, slot_ids, scatter_pos,
              mask, cfg: LlamaConfig, attend_cache: bool, tp_axis: str):
    """One decoder layer inside a shard_map: heads/ffn are tp-LOCAL
    (column-sharded qkv/gate/up, row-sharded o/down with an explicit
    psum), mirroring what GSPMD derives from llama_param_specs — but
    written manually because the enclosing pipeline stage loop runs
    under shard_map, where there is no partitioner to derive it.
    k_cache/v_cache are this stage's LOCAL layer block rows with local
    kv heads: (Slots, S, Hkv/tp, D)."""
    B, T, H = x.shape
    D = cfg.hd

    h = rms_norm(x, _nw(lp["attn_norm"], cfg), cfg.rms_norm_eps)
    q = qmatmul(h, lp["wq"])  # (B, T, Hq*D/tp)
    k = qmatmul(h, lp["wk"])
    v = qmatmul(h, lp["wv"])
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    Hq_l = q.shape[-1] // D
    Hkv_l = k.shape[-1] // D
    q = q.reshape(B, T, Hq_l, D)
    k = k.reshape(B, T, Hkv_l, D)
    v = v.reshape(B, T, Hkv_l, D)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    new_k_cache = new_v_cache = None
    if k_cache is not None:
        rows = (jnp.arange(B) if slot_ids is None else slot_ids)[:, None]
        new_k_cache = k_cache.at[rows, scatter_pos].set(k.astype(k_cache.dtype), mode="drop")
        new_v_cache = v_cache.at[rows, scatter_pos].set(v.astype(v_cache.dtype), mode="drop")

    if attend_cache:
        kc = new_k_cache if slot_ids is None else new_k_cache[slot_ids]
        vc = new_v_cache if slot_ids is None else new_v_cache[slot_ids]
        attn = gqa_attend(q, kc.astype(q.dtype), vc.astype(q.dtype), mask)
    else:
        attn = gqa_attend(q, k, v, mask)
    o_part = qmatmul(attn.reshape(B, T, Hq_l * D), lp["wo"])  # partial over tp
    x = x + jax.lax.psum(o_part, tp_axis)

    h = rms_norm(x, _nw(lp["mlp_norm"], cfg), cfg.rms_norm_eps)
    act = _ACT[cfg.hidden_act]
    d_part = qmatmul(act(qmatmul(h, lp["wg"])) * qmatmul(h, lp["wu"]), lp["wd"])
    x = x + jax.lax.psum(d_part, tp_axis)
    return x, new_k_cache, new_v_cache


@partial(jax.jit, static_argnames=("cfg", "mode", "last_only", "mesh"))
def forward_pp(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # (B, T)
    positions: jnp.ndarray,  # (B, T)
    lengths: jnp.ndarray,  # (B,)
    cache: Params,
    mesh,  # Mesh with a "pp" axis (and optionally "tp")
    mode: str = "prefill",  # "prefill" | "prefill_chunk" | "decode"
    last_only: bool = True,
    slot_ids: jnp.ndarray | None = None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, Params]:
    """SERVING forward with the layer axis sharded over ``pp``.

    This is what lets 70B-class models serve on v5e (SURVEY.md §2.4 PP
    row; round-4 verdict next #6): tp is capped by kv heads (Hkv=8), and
    tp=8 alone leaves 17.5 GiB/chip of bf16 weights — over the 16 GiB
    HBM. Sharding layers over ``pp`` splits weights AND the KV cache by
    stages.

    Unlike forward_pipelined (GPipe microbatch streaming, no cache —
    batch-scoring throughput), this variant is CACHE-FULL and runs the
    stages SEQUENTIALLY per call: stage s applies its local layer block
    (a lax.scan) and writes its local cache rows, then the activation
    hops one stage forward over ICI (ppermute). Only the stage holding
    the live activation computes (lax.cond on axis_index) — each chip
    streams only its own weight shard once per step, which is the whole
    point. The pp "bubble" shows up as stage-serial latency per step;
    decode throughput at large batch stays weight-bandwidth-bound and
    per-chip weight traffic is 1/(tp·pp) of the model.

    tp within a stage is manual Megatron layout (_layer_tp): shard_map
    gives each device its (L/pp, .../tp) block, so the partitioner
    cannot derive the collectives — one psum over "tp" after the o and
    down projections, exactly what GSPMD inserts for the tp-only path.
    """
    B, T = tokens.shape
    pp = mesh.shape["pp"]
    x = params["embed"][tokens] if embeds is None else embeds.astype(params["embed"].dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    inv_freq = rope_inv_freq(cfg.hd, cfg.rope_theta, cfg.rope_scaling_dict)
    cos, sin = rope_cos_sin(positions, inv_freq)

    S = cache["k"].shape[2]
    if mode == "decode":
        mask = decode_mask(S, lengths)
        if cfg.sliding_window:
            span = jnp.arange(S)
            mask = mask & (span[None, None, :] > lengths[:, None, None] - 1 - cfg.sliding_window)
        scatter_pos = positions
    elif mode == "prefill_chunk":
        span = jnp.arange(S)
        mask = (span[None, None, :] <= positions[:, :, None]) & (
            span[None, None, :] < lengths[:, None, None])
        if cfg.sliding_window:
            mask = mask & (span[None, None, :] > positions[:, :, None] - cfg.sliding_window)
        valid = positions < lengths[:, None]
        scatter_pos = jnp.where(valid, positions, S)
    else:
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        mask = causal_prefill_mask(positions, lengths)
        if cfg.sliding_window:
            mask = mask & (positions[:, None, :] > positions[:, :, None] - cfg.sliding_window)
        scatter_pos = jnp.where(valid, positions, S)
    attend_cache = mode in ("decode", "prefill_chunk")

    from jax.sharding import PartitionSpec as P

    from inference_gateway_tpu.parallel.sharding import pp_layer_specs

    layer_specs = pp_layer_specs(cfg, quantized=_is_quantized(params))
    cache_spec = P("pp", None, None, "tp", None)
    rep = P()

    def local_fn(x, layers_local, kc, vc, cos_l, sin_l, mask_l, sids, spos):
        my = jax.lax.axis_index("pp")

        def stage(operand):
            xx, kcc, vcc = operand

            def body(carry, per_layer):
                lp, k_l, v_l = per_layer
                y, nk, nv = _layer_tp(carry, lp, cos_l, sin_l, k_l, v_l, sids,
                                      spos, mask_l, cfg, attend_cache, "tp")
                return y, (nk, nv)

            xx, (nk, nv) = jax.lax.scan(body, xx, (layers_local, kcc, vcc))
            return xx, nk, nv

        for s in range(pp):
            x, kc, vc = jax.lax.cond(
                my == s, stage, lambda o: o, (x, kc, vc))
            if s < pp - 1:
                x = jax.lax.ppermute(x, "pp", [(i, (i + 1) % pp) for i in range(pp)])
        # The finished activation lives on the last stage; replicate it.
        x = jax.lax.psum(jnp.where(my == pp - 1, x, jnp.zeros_like(x)), "pp")
        return x, kc, vc

    x, new_k, new_v = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(rep, layer_specs, cache_spec, cache_spec, rep, rep, rep, rep, rep),
        out_specs=(rep, cache_spec, cache_spec),
        check_vma=False,
    )(x, params["layers"], cache["k"], cache["v"], cos, sin, mask,
      jnp.arange(B, dtype=jnp.int32) if slot_ids is None else slot_ids, scatter_pos)

    x = rms_norm(x, _nw(params["final_norm"], cfg), cfg.rms_norm_eps)
    if last_only:
        if mode == "decode":
            idx = jnp.zeros_like(lengths)
        else:
            idx = jnp.maximum(lengths - 1 - positions[:, 0], 0)
        x = x[jnp.arange(B), idx]
    if cfg.tie_word_embeddings:
        logits = (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)
    else:
        logits = qmatmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


def _is_quantized(params: Params) -> str | None:
    """Which quantization mode the layer stack carries (None = full)."""
    from inference_gateway_tpu.ops.quant import Q4Tensor, QTensor

    w = params["layers"]["wq"]
    if isinstance(w, Q4Tensor):
        return "int4"
    if isinstance(w, QTensor):
        return "int8"
    return None


def init_paged_cache(cfg: LlamaConfig, cache_cfg, dtype=jnp.bfloat16) -> Params:
    """The page pool (serving/kv_cache.PagedCacheConfig): k/v of shape (L, num_pages, page_size, Hkv*D)."""
    shape = (cfg.num_layers, cache_cfg.resolve_num_pages(), cache_cfg.page_size, cfg.num_kv_heads * cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def param_specs(cfg: LlamaConfig) -> dict:
    from inference_gateway_tpu.parallel.sharding import llama_param_specs

    return llama_param_specs(cfg)


def windowed_layers(cfg: LlamaConfig) -> int:
    """How many of the model's layers see only ``sliding_window`` keys."""
    if not cfg.sliding_window:
        return 0
    return sum(cfg.layer_window) if cfg.layer_window else cfg.num_layers


# What an EVA model's step programs count beside their tokens (ISSUE 61): the rows of
# the pool the step's rows read (summaries and exact rows; summed over rows), those
# rows' true contexts in tokens, the windows folded, the (query, row) pairs the
# step's attention scored (a chunk's queries see one row more each), and its queries.
EVA_STATS = ("eva_rows", "eva_context", "eva_rolls", "eva_pairs", "eva_queries")


def _eva_status(cfg: LlamaConfig, view) -> dict:
    """/debug/status ``eva``: the attention's two sizes and, for the longest slot the
    engine admits, the rows and pages it holds against a row a token."""
    out = {"window": cfg.eva_window, "chunk": cfg.eva_chunk, "prefix_hits_declined": view.prefix_hits_declined}
    if view.page_cfg is not None:
        n, page = view.page_cfg.max_seq_len, view.page_cfg.page_size
        out.update(longest_slot={"tokens": n, "cached_rows": int(view.page_cfg.rows(n)),
                                 "pages_held_at_most": view.page_cfg.max_pages_per_slot,
                                 "pages_full_attention": -(-n // page)})
    return {"eva": out}


def family(cfg: LlamaConfig) -> Family:
    """What serving ``cfg`` takes: every layer's k and v in the pool, nothing
    beside it, nothing counted, nothing refused. An EVA model (ISSUE 61) keeps
    the same pool under rows of its own (``cached_rows``): it is admitted through
    the mixed step alone, counts what its rows read, and refuses by name what
    would read the pool a row a token."""
    layers = attention_layers(cfg.num_layers, cfg.sliding_window, windowed_layers(cfg))
    if not cfg.eva_window:
        return Family(module=sys.modules[__name__], attention_layers=layers)
    from inference_gateway_tpu.ops.eva_attention import FoldedRows

    why = "reads the pool a row a token; EVA folds a window into a page of summaries"
    return Family(
        module=sys.modules[__name__], mixed_alone=True, attention_layers=layers,
        refuses={"spec_draft": f"speculative decoding {why}; serve it without --spec-draft",
                 "vision_model": f"multimodal prefill {why}", "int4": INT8_ALONE,
                 "mesh": "no mesh ({shape}) carries the fold yet; serve it on one device"},
        chunk_stats=EVA_STATS, mixed_stats=EVA_STATS,
        cached_rows=FoldedRows(cfg.eva_window, cfg.eva_chunk),
        status=partial(_eva_status, cfg))
