"""Phi-4-mini-flash (SambaY, arXiv:2507.06607): a decoder-hybrid-decoder.

Five kinds of layer in one model, in pairs (an even layer, then an odd
one); with P = num_layers / 2 pairs and the middle pair M = P / 2:

    pair k < M    Mamba mixer                     | differential attention under the window
    pair M        Mamba mixer, emits the memory m | full differential attention: the shared cache
    pair k > M    gated memory unit on m          | cross differential attention: own query,
                                                  | pair M's keys and values

Every block is pre-norm with LayerNorm (gain and bias) and a silu-gated
MLP; no position encoding; the head is the embedding. The equations are
those of bench/references/sambay_diff.py, which tests/test_phi4flash.py
holds this file to.

What it asks of serving, and where that lives:

- **A state beside the page pool.** Each Mamba layer keeps, for every
  slot, S (N x Di float32, the channels in the lanes) and the convolution's last K - 1 inputs. They
  ride the cache pytree (``ssm``, ``conv``), so both step programs carry
  them as they carry the pages (donated, and in the decode chunk's loop
  carry). A segment that starts at position 0 starts from zero
  (ops/ssm.py), which is every (re)admission: recompute-style resume
  after a preemption is right as it is, and no host reset exists.
- **M + 1 caching layers for 2P layers.** The M windowed layers and the
  one full layer write the pool; the cross layers read the full layer's
  pages through the same page table and write nothing. The memory m is an
  activation of the step and is cached nowhere.
- **Differential attention on the paged kernels as they are.** A token's
  cache row is its Hkv key heads of D as they lie, which IS Hkv / 2 heads
  of 2D, [k1_j; k2_j], and likewise [v_2j; v_2j+1]. With the Hq query
  heads zero-padded to 2D, [q_2i; 0] and [0; q_2i+1],
  softmax([q1; 0] . [k1; k2]) = softmax(q1 . k1) exactly and the kernel's
  2D-wide output is A^s v_j: both kernels serve the layer at (Hq, Hkv / 2,
  2D), reading each key and value once. The subtraction, the per-head
  RMSNorm and the scale are plain JAX behind the kernel.
- **Only rows whose logits are needed pass the cross-decoder.** Its
  layers write no cache, so a mixed step gathers each row's last token
  after pair M and runs pairs > M over the rows alone, cross attention
  through the DECODE kernel (one query a row): the family's linear
  prefill, and exact.

Served through the paged mixed step and the decode chunk alone: the
dense-cache forward, the bucketed paged prefill and the prefix-hit tail
program would each need a state hand-over they do not have, and refuse by
name (serving/engine.py routes every admission through the ragged step).
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
from inference_gateway_tpu.ops.quant import qmatmul, seeded_std

Params = dict


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    # Not in the published config.json: the family's convention
    # (configuration_phi4flash.py's defaults; bench/configs/...'s ``assumed``).
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160  # ceil(hidden_size / 16)

    def __post_init__(self):
        assert self.num_layers % 4 == 0, "pairs on both sides of a middle pair"
        assert self.num_heads % 2 == 0 and self.num_kv_heads % 2 == 0, "heads pair up"

    @property
    def hd(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kernel_kv_heads(self) -> int:
        """KV heads as the paged kernels see them: a differential pair of
        ``hd`` columns each is one head of 2·hd."""
        return self.num_kv_heads // 2

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def pairs(self) -> int:
        return self.num_layers // 2

    @property
    def mid(self) -> int:
        return self.pairs // 2

    @property
    def cache_layers(self) -> int:
        """Layers that write the page pool: ``mid`` windowed and one full."""
        return self.mid + 1

    @property
    def state_layers(self) -> int:
        return self.mid + 1

    @property
    def cross_layers(self) -> int:
        return self.pairs - self.mid - 1

    @property
    def state_bytes_per_slot(self) -> int:
        """What one slot holds beside its pages, all Mamba layers, float32."""
        return self.state_layers * 4 * self.d_inner * (self.mamba_d_state + self.mamba_d_conv - 1)

    def param_count(self) -> int:
        H, I, Di = self.hidden_size, self.intermediate_size, self.d_inner
        R, N, K = self.mamba_dt_rank, self.mamba_d_state, self.mamba_d_conv
        kv = self.num_kv_heads * self.hd
        mamba = H * 2 * Di + Di * (R + 2 * N) + R * Di + Di * H + Di * (K + 3 + N)
        self_attn = H * (H + 2 * kv) + H * H + (H + 2 * kv) + H
        cross = 2 * H * H + 2 * H
        per_attn = 4 * self.hd + 2 * self.hd  # lambdas, sub-norm gain
        return (self.vocab_size * H + self.num_layers * (3 * H * I + 4 * H) + 2 * H
                + self.state_layers * (mamba + self_attn) + self.cross_layers * (2 * H * Di + cross)
                + self.pairs * per_attn)


PRESETS: dict[str, Phi4FlashConfig] = {
    "phi-4-mini-flash": Phi4FlashConfig(),
    # All five kinds of layer (3 Mamba, 2 windowed, 1 full, 1 GMU, 1 cross),
    # a window shorter than the tests' contexts, the published 2:1 heads.
    "phi4flash-test-tiny": Phi4FlashConfig(
        vocab_size=256, hidden_size=64, num_layers=8, num_heads=4, num_kv_heads=2,
        intermediate_size=128, sliding_window=16, max_position_embeddings=2048,
        mamba_d_state=8, mamba_dt_rank=4),
}

# Matmul leaves stored int8 per output channel by ``--quantize int8``
# (ops/quant.QUANTIZABLE holds the same names); W_x, W_dt, the convolution,
# A_log, D, the norms, lambdas and biases stay as they are.
QUANT_LEAVES = ("fc1", "fc2", "m_in", "mix_out", "g_in", "wq", "wkv", "wo")


def init_params(rng: jax.Array, cfg: Phi4FlashConfig, dtype=jnp.bfloat16, quant=None) -> Params:
    """Seeded weights, stacked by PAIR (bench/reference.py slices every leaf
    of ``layers`` at one index, so pair k's leaves are slice k; the Mamba
    leaves and ``wkv`` stop at pair M, ``g_in`` starts after it):

        norms (P, 2, 4, H)   even / odd layer; g1, b1, g2, b2
        fc1 (P, 2, H, 2I), fc2 (P, 2, I, H)
        m_in (M+1, H, 2Di), m_conv_w (M+1, K, Di), m_conv_b, m_x (M+1, Di, R+2N),
        m_dt (M+1, R, Di), m_dt_b, m_A_log (M+1, Di, N), m_D
        mix_out (P, Di, H)   the mixer's W_out for k <= M, the GMU's for k > M
        g_in (P-M-1, H, Di)
        wq (P, H, H), bq, wkv (M+1, H, 2 Hkv D), bkv, wo (P, H, H), bo,
        lam (P, 4, D) lq1 lk1 lq2 lk2, sub_norm (P, 2D)
        final_norm (2, H)    gain, bias

    The recurrence's leaves take the published initialisation: A_log =
    log(1..N) a row, D = 1, b_dt the inverse softplus of a step log-uniform
    in [0.001, 0.1], lambdas normal(0, 0.1); so a state holds memory over
    tens to thousands of tokens and a stale or leaked one shows in the
    logits. ``quant`` (a matrix -> QTensor) builds each QUANT_LEAVES leaf
    quantised, one stack entry at a time, so no full-precision leaf exists.
    """
    H, I, V, Di = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.d_inner
    R, N, K, D = cfg.mamba_dt_rank, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.hd
    P, Sm, G = cfg.pairs, cfg.state_layers, cfg.cross_layers
    kv = cfg.num_kv_heads * D
    names = ("embed", "fc1", "fc2", "m_in", "m_conv_w", "m_x", "m_dt", "m_dt_step", "mix_out", "g_in",
             "wq", "wkv", "wo", "lam")
    keys = dict(zip(names, jax.random.split(rng, len(names))))

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def one(key, shape, std, quantise):
        w = (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
        return quant(w) if quantise else w

    def stack(name, shape):
        """Leaf ``name`` of stacked ``shape``, entry by entry of its first axis."""
        per = [one(jax.random.fold_in(keys[name], i), shape[1:], seeded_std(cfg, name),
                   quant is not None and name in QUANT_LEAVES) for i in range(shape[0])]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    norms = jnp.broadcast_to(jnp.asarray([1.0, 0.0, 1.0, 0.0], dtype)[None, None, :, None], (P, 2, 4, H))
    dt = jnp.exp(jax.random.uniform(keys["m_dt_step"], (Sm, Di), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    layers = {
        "norms": norms + jnp.zeros((), dtype),
        "fc1": stack("fc1", (P, 2, H, 2 * I)),
        "fc2": stack("fc2", (P, 2, I, H)),
        "m_in": stack("m_in", (Sm, H, 2 * Di)),
        "m_conv_w": stack("m_conv_w", (Sm, K, Di)).astype(jnp.float32),
        "m_conv_b": jnp.zeros((Sm, Di), jnp.float32),
        "m_x": stack("m_x", (Sm, Di, R + 2 * N)),
        "m_dt": stack("m_dt", (Sm, R, Di)),
        "m_dt_b": dt + jnp.log(-jnp.expm1(-dt)),                       # softplus^-1(dt)
        "m_A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (Sm, Di, N)) + 0.0,
        "m_D": jnp.ones((Sm, Di), jnp.float32),
        "mix_out": stack("mix_out", (P, Di, H)),
        "g_in": stack("g_in", (G, H, Di)),
        "wq": stack("wq", (P, H, H)),
        "bq": jnp.zeros((P, H), dtype),
        "wkv": stack("wkv", (Sm, H, 2 * kv)),
        "bkv": jnp.zeros((Sm, 2 * kv), dtype),
        "wo": stack("wo", (P, H, H)),
        "bo": jnp.zeros((P, H), dtype),
        "lam": stack("lam", (P, 4, D)).astype(jnp.float32),
        "sub_norm": jnp.ones((P, 2 * D), dtype),
    }
    final = jnp.stack([jnp.ones((H,), dtype), jnp.zeros((H,), dtype)])
    return {"embed": one(keys["embed"], (V, H), seeded_std(cfg, "embed"), False),
            "layers": layers, "final_norm": final}


def init_paged_cache(cfg: Phi4FlashConfig, cache_cfg, dtype=jnp.bfloat16) -> Params:
    """Pages for the ``cache_layers`` layers that write them, k/v
    (Lc, num_pages, page_size, Hkv*D), and the per-slot state store beside
    them: ``ssm`` (Lm, slots, N, Di) and ``conv`` (Lm, slots, K-1, Di), float32
    (a state's channels in the lanes: ops/ssm.py)."""
    pages = cache_cfg.resolve_num_pages()
    shape = (cfg.cache_layers, pages, cache_cfg.page_size, cfg.num_kv_heads * cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), **init_state(cfg, cache_cfg.max_slots)}


def init_state(cfg: Phi4FlashConfig, slots: int) -> Params:
    return {"ssm": jnp.zeros((cfg.state_layers, slots, cfg.mamba_d_state, cfg.d_inner), jnp.float32),
            "conv": jnp.zeros((cfg.state_layers, slots, cfg.mamba_d_conv - 1, cfg.d_inner), jnp.float32)}


def init_cache(cfg: Phi4FlashConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    """The dense slot cache of an engine that is built for its tree alone
    (the benchmark's reference child): nothing steps over it (``forward``)."""
    shape = (cfg.cache_layers, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), **init_state(cfg, batch)}


def forward(*_args, **_kwargs):
    raise NotImplementedError(
        "phi4flash is served over the paged cache alone (--attention paged): the dense-cache forward "
        "carries no Mamba state")


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def layer_norm(x, gain, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def _mlp(x, norms, layers: Params, k, which: int, eps):
    """x plus the MLP of pair ``k``'s even (``which`` 0) or odd (1) layer. Its two
    matrices are indexed out of the stacks at (k, which) in ONE step, the
    (P, 2, ...) leaf seen as (2P, ...): a pair's slice taken first is a copy
    of both layers' int8 matrices that the matmuls then read again (0.2 ms
    a pair on a v5e: PERF.md section 6, PR 32)."""
    with jax.named_scope("mlp"):  # the block whole, its residual add included, as models/llama.py's encloses it
        fc1, fc2 = (jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a.reshape((-1,) + a.shape[2:]), 2 * k + which, 0, keepdims=False), layers[n]) for n in ("fc1", "fc2"))
        g, u = jnp.split(qmatmul(layer_norm(x, norms[2], norms[3], eps), fc1), 2, axis=-1)
        return x + qmatmul(u * jax.nn.silu(g), fc2)


def lambda_init(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def pad_queries(q):
    """(T, Hq, D) -> (T, Hq, 2D): even heads [q; 0], odd heads [0; q], times
    sqrt(2) so that a kernel scaling by (2D)^-1/2 scales by D^-1/2."""
    zero = jnp.zeros_like(q)
    odd = (jnp.arange(q.shape[1]) % 2 == 1)[None, :, None]
    q = (q.astype(jnp.float32) * math.sqrt(2.0)).astype(q.dtype)
    return jnp.where(odd, jnp.concatenate([zero, q], axis=-1), jnp.concatenate([q, zero], axis=-1))


def diff_combine(o, lam, gain, layer, eps=1e-5):
    """The kernel's (T, Hq, 2D) output, A^1_i v_j on even heads and A^2_i v_j
    on odd ones, -> (T, Hq * D): the difference, the per-head RMSNorm and
    the (1 - lambda_init) scale."""
    T, Hq, D2 = o.shape
    linit = lambda_init(layer)
    lq1, lk1, lq2, lk2 = lam.astype(jnp.float32)
    full = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + linit
    o = o.astype(jnp.float32).reshape(T, Hq // 2, 2, D2)
    a = o[:, :, 0] - full * o[:, :, 1]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps) * gain.astype(jnp.float32)
    return (a * (1.0 - linit)).reshape(T, Hq // 2 * D2)


# The mixer's leaves under the names ``ops/ssm.mamba_mixer`` reads (no inner norms here).
MIXER_NAMES = {"in_proj": "m_in", "conv_w": "m_conv_w", "conv_b": "m_conv_b", "x_proj": "m_x", "dt_proj": "m_dt",
               "dt_bias": "m_dt_b", "A_log": "m_A_log", "D": "m_D", "out_proj": "mix_out"}


def _take(leaves: Params, names, index):
    """Entry ``index`` (traced or not) of each named stacked leaf."""
    return {n: jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False), leaves[n])
            for n in names}


PAIR_LEAVES = ("norms", "fc1", "fc2", "mix_out", "wq", "bq", "wo", "bo", "lam", "sub_norm")  # stacked P deep
TAKEN = tuple(n for n in PAIR_LEAVES if n not in ("fc1", "fc2"))  # what a pair slices whole (``_mlp`` takes its own)
MAMBA_LEAVES = ("m_in", "m_conv_w", "m_conv_b", "m_x", "m_dt", "m_dt_b", "m_A_log", "m_D", "wkv", "bkv")


def _decoder(params: Params, cfg: Phi4FlashConfig, x, cache: Params, write_idx, page_table,
             conv, scan, attend_self, rows, lengths):
    """Both halves over rows x (T, H): the self-decoder (pairs <= M) over
    all T rows with the step's own attention and state operations, then
    ``rows(x, m)`` picks the rows that go on (all of them in decode; each
    slot's last token in a mixed step) and the cross-decoder runs over
    those, one query a row through the decode kernel at ``lengths``.
    Returns the rows' final hidden states and the new cache."""
    from inference_gateway_tpu.ops.paged_attention import paged_attention

    layers = params["layers"]
    eps = cfg.layer_norm_eps
    Hq, Hkv2, D = cfg.num_heads, cfg.kernel_kv_heads, cfg.hd
    Lc, P, page_size, HkvD = cache["k"].shape
    flat = P * page_size
    total = Lc * flat
    M = cfg.mid

    def self_pair(carry, k):
        x, ck, cv, sstates, ctails, _ = carry
        with jax.named_scope("mamba"):  # (the pair's leaves are taken under its first block's name)
            lp = {**_take(layers, TAKEN, k), **_take(layers, MAMBA_LEAVES, k)}
            n_even, n_odd = lp["norms"][0], lp["norms"][1]
            out, y, store = ssm.mamba_mixer(
                layer_norm(x, n_even[0], n_even[1], eps), {n: lp[m] for n, m in MIXER_NAMES.items()},
                {"ssm": sstates, "conv": ctails}, k, conv, scan)
            sstates, ctails = store["ssm"], store["conv"]
            x = x + out
        x = _mlp(x, n_even, layers, k, 0, eps)
        with jax.named_scope("diff_attn"):
            h = layer_norm(x, n_odd[0], n_odd[1], eps)
            q = (qmatmul(h, lp["wq"]) + lp["bq"]).reshape(-1, Hq, D)
            kk, vv = jnp.split(qmatmul(h, lp["wkv"]) + lp["bkv"], 2, axis=-1)
            # Rows that were out of bounds inside the layer must stay out of
            # bounds of the WHOLE buffer (models/llama.py's convention).
            w_idx = jnp.where(write_idx >= flat, total, write_idx + k * flat)
            ck = ck.at[w_idx].set(kk.astype(ck.dtype), mode="drop")
            cv = cv.at[w_idx].set(vv.astype(cv.dtype), mode="drop")
            pages = (ck.reshape(Lc * P, page_size, HkvD), cv.reshape(Lc * P, page_size, HkvD))
            q, table = pad_queries(q), page_table + k * P
            o = attend_self(q, *pages, table, k < M)  # pairs under M are windowed, pair M is full
            a = diff_combine(o, lp["lam"], lp["sub_norm"], 2 * k + 1)
            x = x + qmatmul(a.astype(x.dtype), lp["wo"]) + lp["bo"]
        x = _mlp(x, n_odd, layers, k, 1, eps)
        return (x, ck, cv, sstates, ctails, y), None

    # One loop over the pairs 0..M (a pair M of its own would index the
    # stacks at a constant, and XLA then copies that pair's matrices out of
    # them before it multiplies). The carry's last entry is the pair's y:
    # after the loop, pair M's, the memory m.
    carry = (x, cache["k"].reshape(total, HkvD), cache["v"].reshape(total, HkvD), cache["ssm"], cache["conv"],
             jnp.zeros((x.shape[0], cfg.d_inner), jnp.float32))
    (x, ck, cv, sstates, ctails, m), _ = jax.lax.scan(self_pair, carry, jnp.arange(M + 1))
    new_cache = {"k": ck.reshape(Lc, P, page_size, HkvD), "v": cv.reshape(Lc, P, page_size, HkvD),
                 "ssm": sstates, "conv": ctails}

    with jax.named_scope("head"):  # the rows that go on: models/llama.py's ``head`` picks its rows too
        x, m = rows(x, m)
    pages_k, pages_v = new_cache["k"].reshape(Lc * P, page_size, HkvD), new_cache["v"].reshape(Lc * P, page_size, HkvD)
    shared_table = page_table + M * P

    def cross_pair(x, k):
        with jax.named_scope("gmu"):
            lp = {**_take(layers, TAKEN, k), **_take(layers, ("g_in",), k - M - 1)}
            n_even, n_odd = lp["norms"][0], lp["norms"][1]
            gate = qmatmul(layer_norm(x, n_even[0], n_even[1], eps), lp["g_in"])
            x = x + qmatmul((m * jax.nn.silu(gate.astype(jnp.float32))).astype(x.dtype), lp["mix_out"])
        x = _mlp(x, n_even, layers, k, 0, eps)
        with jax.named_scope("diff_attn"):
            h = layer_norm(x, n_odd[0], n_odd[1], eps)
            q = (qmatmul(h, lp["wq"]) + lp["bq"]).reshape(-1, Hq, D)
            o = paged_attention(pad_queries(q), pages_k, pages_v, shared_table, lengths, Hkv2)
            a = diff_combine(o, lp["lam"], lp["sub_norm"], 2 * k + 1)
            x = x + qmatmul(a.astype(x.dtype), lp["wo"]) + lp["bo"]
        x = _mlp(x, n_odd, layers, k, 1, eps)
        return x, None

    x, _ = jax.lax.scan(cross_pair, x, jnp.arange(M + 1, cfg.pairs))
    return x, new_cache


def _head(params: Params, cfg: Phi4FlashConfig, x):
    with jax.named_scope("head"):
        fn = params["final_norm"]
        x = layer_norm(x, fn[0], fn[1], cfg.layer_norm_eps)
        return (x @ params["embed"].T.astype(x.dtype)).astype(jnp.float32)


def window_pages(cfg: Phi4FlashConfig, page_size: int, first_len, last_len):
    """``llama.window_pages`` for this model's ``mid`` windowed layers."""
    return llama.window_pages(cfg.mid, cfg.sliding_window, page_size, first_len, last_len)


@partial(jax.jit, static_argnames=("cfg", "mode", "last_only", "mesh", "with_aux"))
def forward_paged(params: Params, cfg: Phi4FlashConfig, tokens, positions, lengths, cache: Params,
                  write_idx, page_table, mode: str = "decode", last_only: bool = True, mesh=None,
                  valid=None, with_aux: bool = False):
    """One decode step over the paged cache and the state store: every row
    a slot, one token each (``models/llama.forward_paged``'s contract for
    ``mode="decode"``). ``valid`` (B,) rows that carry a stream: the others
    keep their state. With ``with_aux`` a third result, ``window_pages``."""
    from inference_gateway_tpu.ops.paged_attention import GLOBAL_WINDOW, paged_attention

    if mode != "decode":
        raise NotImplementedError(
            f"phi4flash forward_paged(mode={mode!r}): prompts are admitted through the ragged mixed step "
            "alone (the bucketed and prefix-tail programs carry no Mamba state)")
    assert mesh is None, "phi4flash: no mesh carries the Mamba state yet"
    B = tokens.shape[0]
    valid = jnp.ones((B,), bool) if valid is None else valid

    def attend(q, pages_k, pages_v, table, windowed):
        # The decode kernel takes the window as an operand: ONE instance for
        # both kinds of layer (ISSUE 37; models/llama._window_operand).
        window = jnp.where(windowed, cfg.sliding_window, GLOBAL_WINDOW).astype(jnp.int32)
        return paged_attention(q, pages_k, pages_v, table, lengths, cfg.kernel_kv_heads,
                               window_dyn=window)

    with jax.named_scope("embed"):
        x = params["embed"][tokens[:, 0]]
    x, cache = _decoder(
        params, cfg, x, cache, write_idx[:, 0], page_table,
        conv=lambda u, tails, w, b: ssm.conv_decode(u, tails, w, b, valid),
        scan=ssm.decode_scan(valid),
        attend_self=attend, rows=lambda x, m: (x, m), lengths=lengths)
    logits = _head(params, cfg, x)
    if not with_aux:
        return logits, cache
    with jax.named_scope("carry"):
        live = jnp.where(valid, lengths, 0)
        return logits, cache, window_pages(cfg, cache["k"].shape[2], live, live)


@partial(jax.jit, static_argnames=("cfg", "mesh", "with_aux"))
def forward_ragged(params: Params, cfg: Phi4FlashConfig, tokens, positions, cache: Params, write_idx,
                   page_table, q_starts, q_lens, kv_lens, mesh=None, with_aux: bool = False):
    """One mixed step (``models/llama.forward_ragged``'s contract): the
    packed tokens of prompt chunks and decode rows through the
    self-decoder, each segment from its slot's state; then each row's last
    token through the cross-decoder. Returns per-row logits (R, V) and the
    new cache; with ``with_aux`` also ``window_pages``."""
    from inference_gateway_tpu.ops.paged_attention import GLOBAL_WINDOW, ragged_paged_attention

    assert mesh is None, "phi4flash: no mesh carries the Mamba state yet"
    T = tokens.shape[1]
    with jax.named_scope("carry"):  # the rows' segments and the chunk kernel's plan, once a step for every layer
        scan, seg, fresh, _ = ssm.ragged_scan(q_starts, q_lens, kv_lens, T)
        last = jnp.clip(q_starts + q_lens - 1, 0, T - 1)
        live_lens = jnp.where(q_lens > 0, kv_lens, 0)

    def attend(q, pages_k, pages_v, table, windowed):
        # The ragged kernel takes the window as an operand: ONE instance for
        # both kinds of layer (ISSUE 34; models/llama._window_operand).
        window = jnp.where(windowed, cfg.sliding_window, GLOBAL_WINDOW).astype(jnp.int32)
        return ragged_paged_attention(q, pages_k, pages_v, table, q_starts, q_lens, kv_lens,
                                      cfg.kernel_kv_heads, window_dyn=window)

    with jax.named_scope("embed"):
        x = params["embed"][tokens[0]]
    x, cache = _decoder(
        params, cfg, x, cache, write_idx[0], page_table,
        conv=lambda u, tails, w, b: ssm.conv_ragged(u, tails, w, b, seg, fresh, q_starts, q_lens),
        scan=scan,
        attend_self=attend, rows=lambda x, m: (x[last], m[last]), lengths=live_lens)
    logits = _head(params, cfg, x)
    if not with_aux:
        return logits, cache
    with jax.named_scope("carry"):
        return logits, cache, window_pages(cfg, cache["k"].shape[2], live_lens - q_lens + 1, live_lens)


# Rows of both step programs' fetch block (ISSUE 32): the pages the windowed
# layers read and the pages their rows' contexts hold (``window_pages``).
WINDOW_STATS = ("window_pages_read", "context_pages")


def _status(cfg: Phi4FlashConfig, view) -> dict:
    """``Family.status``: the state store, the layers that write the page pool and who
    reads the shared one, and the two counters (segments started from zero; prefix
    hits declined)."""
    return {"state_store": {"slots": view.max_slots, "layers": cfg.state_layers,
                            "bytes": view.max_slots * cfg.state_bytes_per_slot},
            "cache_layers": {"window": cfg.mid, "full": 1, "shared_readers": cfg.cross_layers},
            "state_resets": view.state_resets, "prefix_hits_declined": view.prefix_hits_declined}


def family(cfg: Phi4FlashConfig) -> Family:
    """What serving ``cfg`` takes. What cannot carry the Mamba state is refused by
    name (ISSUE 32)."""
    return Family(
        module=sys.modules[__name__], state_store=True, mixed_alone=True, row_writes=False,
        refuses={"spec_draft": "speculative decoding has no Mamba state to roll back; serve it without --spec-draft",
                 "vision_model": "multimodal prefill carries no Mamba state", "int4": INT8_ALONE,
                 "mesh": "no mesh ({shape}) carries the per-slot state store yet; serve it on one device"},
        chunk_stats=WINDOW_STATS, mixed_stats=WINDOW_STATS,
        attention_layers=attention_layers(cfg.cache_layers, cfg.sliding_window, cfg.mid),
        status=partial(_status, cfg))
