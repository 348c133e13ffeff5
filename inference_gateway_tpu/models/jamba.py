"""Jamba (arXiv:2403.19887; ``model_type`` ``jamba``, ISSUE 64): Mamba-1 mixers
(arXiv:2312.00752) beside a few grouped-query attention layers with NO position
encoding in one stack, a dense gated MLP in every layer (``num_experts`` 1), a
tied head. Layer l is an attention layer where ``(l - attn_layer_offset) %
attn_layer_period == 0``, else a Mamba layer whose Delta, B and C are each
RMS-normed behind W_x (the family's ``dt_layernorm``, ``b_layernorm``,
``c_layernorm``):

    x <- x + mixer_l(rmsnorm(x; attn_norm));   x <- x + W_d(silu(W_g h) * (W_u h)),  h = rmsnorm(x; mlp_norm)
    Mamba:  [u, z] = W_in h;  c_t = silu(b + sum_j w[j] u_{t-K+1+j});  [dt, B, C]_t = W_x c_t, each normed
            Delta_t = softplus(W_dt dt + b_dt);  S_t = exp(Delta_t A) * S_{t-1} + (Delta_t c_t) B_t^T
            y_t = S_t C_t + D * c_t;  out = W_out (y_t * silu(z_t))

Why a module of its own, on ``LlamaConfig``: ``models/mixtral.py``'s trees hold a
router and expert stacks in every body layer and this model has neither, and
``models/llama.py``'s trees hold one kind of layer; what this file adds to either
is the config, the tree and the mixer. It is served through the layer loop every
other model shares (``llama.forward_paged_impl`` / ``forward_ragged_impl``), as
the fifth value of their per-layer KIND (``kinds=``, ISSUE 43): the attention
operators' and the mixers' leaves are stacks of their own read at a layer's
ordinal among its kind, the page pool holds the attention layers' planes alone,
and beside it the state store ``{"ssm": (mamba layers, slots, N, Di), "conv":
(mamba layers, slots, K - 1, Di)}`` float32. The layers are scanned in RUNS of
one kind (``llama._layer_groups``), so no ``lax.cond`` stands around the store,
and the MLP's matrices, which a run's scan would slice, are read whole at the
layer's index. The mixer body is ``ops/ssm.mamba_mixer``, the one
``models/phi4flash.py`` calls; a mixed step's rows of more than one token go
through ``ops/ssm.scan_chunk`` (the Pallas kernel ``ssm_scan_chunk_kernel`` on a
TPU), its one-token rows and the decode chunk's through the one-token form
(``ssm_scan_decode_kernel`` there, ``scan_decode`` elsewhere).

Admitted through the mixed step alone; a segment that starts at position 0
starts from zero (ops/ssm.py), so recompute-style resume after a preemption is
right as it is. The dense-cache ``forward`` carries no state and refuses.
"""

from __future__ import annotations

import math
import sys
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from inference_gateway_tpu.models import llama
from inference_gateway_tpu.models.family import INT8_ALONE, Family, attention_layers
from inference_gateway_tpu.ops import ssm
from inference_gateway_tpu.ops.norms import rms_norm
from inference_gateway_tpu.ops.quant import qmatmul, seeded_std

Params = dict


@dataclass(frozen=True)
class JambaConfig(llama.LlamaConfig):
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160

    def __post_init__(self):
        if self.layer_rope is None:   # no position encoding of any kind
            object.__setattr__(self, "layer_rope", (0,) * self.num_layers)
        super().__post_init__()
        if not 0 < self.cache_layers < self.num_layers:
            raise ValueError(f"attn_layer_offset {self.attn_layer_offset}, attn_layer_period {self.attn_layer_period}: "
                             f"{self.cache_layers} attention layers of {self.num_layers}; both kinds must be present")

    @property
    def layer_kinds(self) -> tuple:
        """One flag a layer: 0 an attention layer, 1 a Mamba layer."""
        return tuple(int((l - self.attn_layer_offset) % self.attn_layer_period != 0) for l in range(self.num_layers))

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def state_layers(self) -> int:
        return sum(self.layer_kinds)

    @property
    def cache_layers(self) -> int:
        """Layers that write the page pool: the attention layers."""
        return self.num_layers - self.state_layers

    @property
    def state_bytes_per_slot(self) -> int:
        """What one slot holds beside its pages, all Mamba layers, float32."""
        return self.state_layers * 4 * self.d_inner * (self.mamba_d_state + self.mamba_d_conv - 1)

    def param_count(self) -> int:
        H, I, Di = self.hidden_size, self.intermediate_size, self.d_inner
        R, N, K = self.mamba_dt_rank, self.mamba_d_state, self.mamba_d_conv
        q, kv = self.num_heads * self.hd, self.num_kv_heads * self.hd
        mamba = H * 2 * Di + Di * (R + 2 * N) + R * Di + Di * H + Di * (K + 3 + N) + R + 2 * N
        return (self.vocab_size * H + self.num_layers * (3 * H * I + 2 * H) + H
                + self.state_layers * mamba + self.cache_layers * (2 * H * q + 2 * H * kv))


PRESETS: dict[str, JambaConfig] = {
    # ai21labs/AI21-Jamba2-3B, whole: 28 layers, attention at 7 and 21.
    "jamba2-3b": JambaConfig(
        vocab_size=65536, hidden_size=2560, num_layers=28, num_heads=20, num_kv_heads=1, head_dim=128,
        intermediate_size=8192, rms_norm_eps=1e-6, max_position_embeddings=262144, tie_word_embeddings=True),
    # m a m m m a m: both kinds in runs of one and of more, a group of 4 over ONE KV head.
    "jamba-test-tiny": JambaConfig(
        vocab_size=256, hidden_size=64, num_layers=7, num_heads=4, num_kv_heads=1, head_dim=16,
        intermediate_size=128, rms_norm_eps=1e-6, max_position_embeddings=2048, tie_word_embeddings=True,
        attn_layer_period=4, attn_layer_offset=1, mamba_d_state=8, mamba_dt_rank=4),
}

MLP_LEAVES = ("wg", "wu", "wd")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
MAMBA_LEAVES = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias", "A_log", "D",
                "dt_norm", "b_norm", "c_norm", "out_proj")
# Stored int8 per output channel by ``--quantize int8`` (names of ops/quant.QUANTIZABLE);
# W_x, W_dt, the convolution, A_log, D, every norm and the tied embedding stay as they are.
QUANT_LEAVES = ("in_proj", "out_proj") + ATTENTION_LEAVES + MLP_LEAVES


def init_params(rng: jax.Array, cfg: JambaConfig, dtype=jnp.bfloat16, quant=None) -> Params:
    """Seeded weights in three stacks: every layer's two norms and MLP (leading
    axis num_layers), the attention operators (cache_layers) and the mixers
    (state_layers). The recurrence's leaves take the published initialisation
    (``models/phi4flash.init_params``: A_log = log(1..N) a row, D = 1, b_dt the
    inverse softplus of a step log-uniform in [0.001, 0.1]), the inner norms'
    gains as ``ops/quant._jamba_std`` sizes them, so a state holds memory over tens to thousands of tokens and a
    stale one shows in the logits. ``quant`` (a matrix -> QTensor) builds each
    QUANT_LEAVES leaf quantised, one stack entry at a time."""
    H, I, V, Di = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.d_inner
    R, N, K = cfg.mamba_dt_rank, cfg.mamba_d_state, cfg.mamba_d_conv
    L, La, Lm = cfg.num_layers, cfg.cache_layers, cfg.state_layers
    q, kv = cfg.num_heads * cfg.hd, cfg.num_kv_heads * cfg.hd
    names = ("embed", "dt_step", "conv_w", "x_proj", "dt_proj") + QUANT_LEAVES
    keys = dict(zip(names, jax.random.split(rng, len(names))))

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def one(key, shape, std, quantise):
        w = (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
        return quant(w) if quantise else w

    def stack(name, shape, leaf=None):
        per = [one(jax.random.fold_in(keys[name], i), shape[1:], seeded_std(cfg, leaf or name),
                   quant is not None and name in QUANT_LEAVES) for i in range(shape[0])]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    dt = jnp.exp(jax.random.uniform(keys["dt_step"], (Lm, Di), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    layers = {
        "attn_norm": jnp.ones((L, H), dtype), "mlp_norm": jnp.ones((L, H), dtype),
        "wg": stack("wg", (L, H, I)), "wu": stack("wu", (L, H, I)), "wd": stack("wd", (L, I, H)),
        "wq": stack("wq", (La, H, q)), "wk": stack("wk", (La, H, kv)), "wv": stack("wv", (La, H, kv)),
        "wo": stack("wo", (La, q, H)),
        "in_proj": stack("in_proj", (Lm, H, 2 * Di)),
        "conv_w": stack("conv_w", (Lm, K, Di), "m_conv_w").astype(jnp.float32),   # (ops/quant.seeded_std's name for the taps)
        "conv_b": jnp.zeros((Lm, Di), jnp.float32),
        "x_proj": stack("x_proj", (Lm, Di, R + 2 * N)),
        "dt_proj": stack("dt_proj", (Lm, R, Di)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),                       # softplus^-1(dt)
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (Lm, Di, N)) + 0.0,
        "D": jnp.ones((Lm, Di), jnp.float32),
        # the inner norms' gains as the seeded build sizes them (ops/quant._jamba_std: B's and C's are not 1)
        **{n: jnp.full((Lm, w), seeded_std(cfg, n), dtype) for n, w in (("dt_norm", R), ("b_norm", N), ("c_norm", N))},
        "out_proj": stack("out_proj", (Lm, Di, H)),
    }
    return {"embed": one(keys["embed"], (V, H), seeded_std(cfg, "embed"), False),
            "layers": layers, "final_norm": jnp.ones((H,), dtype)}


def init_state(cfg: JambaConfig, slots: int) -> Params:
    return {"ssm": jnp.zeros((cfg.state_layers, slots, cfg.mamba_d_state, cfg.d_inner), jnp.float32),
            "conv": jnp.zeros((cfg.state_layers, slots, cfg.mamba_d_conv - 1, cfg.d_inner), jnp.float32)}


def init_paged_cache(cfg: JambaConfig, cache_cfg, dtype=jnp.bfloat16) -> Params:
    """Pages for the attention layers alone, k/v (cache_layers, pages, page_size,
    Hkv * D), and the per-slot state store beside them (``init_state``)."""
    shape = (cfg.cache_layers, cache_cfg.resolve_num_pages(), cache_cfg.page_size, cfg.num_kv_heads * cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), **init_state(cfg, cache_cfg.max_slots)}


def init_cache(cfg: JambaConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    """The dense slot cache of an engine that is built for its tree alone (the
    benchmark's reference child): nothing steps over it (``forward``)."""
    shape = (cfg.cache_layers, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), **init_state(cfg, batch)}


def forward(*_args, **_kwargs):
    raise NotImplementedError(
        "jamba is served over the paged cache alone (--attention paged): the dense-cache forward carries no Mamba state")


def _seam(params: Params, cfg: JambaConfig, conv, scan) -> dict:
    """What the shared skeletons take: the dense MLP as the FFN seam, its
    matrices read whole at the layer's index, and the Mamba mixer as the
    per-layer kind, with the step's forms of its two state operations."""
    layers = params["layers"]

    def ffn(x, attn_in, lp, li, valid):
        del attn_in, valid
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        wg, wu, wd = (jax.tree.map(lambda a: a[li], layers[n]) for n in MLP_LEAVES)
        return qmatmul(jax.nn.silu(qmatmul(h, wg)) * qmatmul(h, wu), wd)

    def mix(h, mp, store, ordinal):
        with jax.named_scope("mamba"):  # the projections, the inner norms and what stands between the state operations' own scopes
            out, _, store = ssm.mamba_mixer(h, mp, store, ordinal, conv, scan, cfg.rms_norm_eps)
            return out, store

    kinds = {"state": cfg.layer_kinds, "cache": ("conv", "ssm"), "mix": mix, "runs": True,
             "attention": {n: layers[n] for n in ATTENTION_LEAVES}, "leaves": {n: layers[n] for n in MAMBA_LEAVES}}
    return {"ffn": ffn, "unscanned": MLP_LEAVES + ATTENTION_LEAVES + MAMBA_LEAVES, "kinds": kinds}


# Rows of both step programs' fetch block (``Family.chunk_stats`` / ``mixed_stats``):
# the tokens and the rows the chunk form walked, over the Mamba layers (the
# ``ssm_scan_chunk_kernel``'s work), and the rows the one-token form served (a row a
# Mamba layer reads and writes its state once: ``state_rows`` as every state family's),
# and (ISSUE 65) the static bound of the chunk plan's visits over the Mamba layers
# (``ops/ssd.max_visits``; 0 in a decode chunk): the kernel's grid runs the live ones.
SSM_STATS = ("ssm_chunk_tokens", "ssm_chunk_rows", "state_rows", "chunk_visits_bound")


@partial(jax.jit, static_argnames=("cfg", "mode", "last_only", "mesh", "with_aux"))
def forward_paged(params: Params, cfg: JambaConfig, tokens, positions, lengths, cache: Params,
                  write_idx, page_table, mode: str = "decode", last_only: bool = True, mesh=None,
                  valid=None, with_aux: bool = False):
    """One decode step over the paged cache and the state store
    (``llama.forward_paged``'s contract for ``mode="decode"``). ``valid`` (B,)
    rows that carry a stream: the others keep their state. With ``with_aux`` a
    third result, ``SSM_STATS``."""
    assert mesh is None, "jamba: no mesh carries the Mamba state yet"
    with jax.named_scope("carry"):
        rows = jnp.ones(lengths.shape, bool) if valid is None else valid
    conv = lambda u, tails, w, b: ssm.conv_decode(u, tails, w, b, rows)
    out = llama.forward_paged_impl(params, cfg, tokens, positions, lengths, cache, write_idx, page_table,
                                   mode, last_only, None, valid=valid, **_seam(params, cfg, conv, ssm.decode_scan(rows)))
    if not with_aux:
        return out
    with jax.named_scope("carry"):
        return (*out, jnp.stack([0.0, 0.0, jnp.sum(rows & (lengths > 0)).astype(jnp.float32), 0.0]))


@partial(jax.jit, static_argnames=("cfg", "mesh", "with_aux"))
def forward_ragged(params: Params, cfg: JambaConfig, tokens, positions, cache: Params, write_idx,
                   page_table, q_starts, q_lens, kv_lens, mesh=None, with_aux: bool = False):
    """One mixed step (``llama.forward_ragged``'s contract): every row's segment
    from its slot's state, from zero where it starts at position 0. With
    ``with_aux`` a third result, ``SSM_STATS``."""
    assert mesh is None, "jamba: no mesh carries the Mamba state yet"
    with jax.named_scope("carry"):  # the rows' segments and the chunk kernel's plan: once a step, for every Mamba layer
        scan, seg, fresh, counts = ssm.ragged_scan(q_starts, q_lens, kv_lens, tokens.shape[1])
    conv = lambda u, tails, w, b: ssm.conv_ragged(u, tails, w, b, seg, fresh, q_starts, q_lens)
    out = llama.forward_ragged_impl(params, cfg, tokens, positions, cache, write_idx, page_table,
                                    q_starts, q_lens, kv_lens, None, **_seam(params, cfg, conv, scan))
    if not with_aux:
        return out
    with jax.named_scope("carry"):
        return (*out, counts * jnp.asarray([cfg.state_layers, cfg.state_layers, 1.0, cfg.state_layers], jnp.float32))


def _status(cfg: JambaConfig, view) -> dict:
    """``Family.status``: the state store (a slot's bytes and all), the two
    kinds of layer, how the selective scan is done, and the two counters."""
    return {"state_store": {"slots": view.max_slots, "layers": cfg.state_layers,
                            "bytes_per_slot": cfg.state_bytes_per_slot,
                            "bytes": view.max_slots * cfg.state_bytes_per_slot},
            "cache_layers": {"full": cfg.cache_layers, "mamba": cfg.state_layers},
            "ssm_scan": {"state": [cfg.mamba_d_state, cfg.d_inner], "inner_norms": ["dt", "b", "c"],
                         "rows_of_more_than_one_token": ssm.chunk_plan(cfg.d_inner, cfg.mamba_d_state)},
            "state_resets": view.state_resets, "prefix_hits_declined": view.prefix_hits_declined}


def family(cfg: JambaConfig) -> Family:
    """What serving ``cfg`` takes. What cannot carry the Mamba state is refused by name."""
    return Family(
        module=sys.modules[__name__], state_store=True, mixed_alone=True,
        refuses={"spec_draft": "speculative decoding has no Mamba state to roll back; serve it without --spec-draft",
                 "vision_model": "it is a text model; image input has no path, serve text",
                 "int4": INT8_ALONE,
                 "mesh": "no mesh ({shape}) carries the per-slot state store yet; serve it on one device"},
        chunk_stats=SSM_STATS, mixed_stats=SSM_STATS,
        attention_layers=attention_layers(cfg.cache_layers, None, 0), status=partial(_status, cfg))
