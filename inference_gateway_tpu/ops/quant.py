"""Weight-only quantization: int8 (per-channel) and int4 (group-wise).

Decode throughput on TPU is HBM-bandwidth-bound by the weight stream;
storing matmul weights as int8 with per-output-channel scales halves
that traffic (and fits Llama-3-8B in a single v5e chip's 16 GB); int4
with group-wise scales halves it again (W4 round-to-nearest, two
nibbles packed per int8 byte along the contraction axis — the standard
AWQ/GPTQ storage granularity, without calibration since the container
has no data). The dequantize chain (shift/mask sign-extend, group
scale) is elementwise on the weight operand, which XLA fuses into the
consuming matmul — weights stream packed out of HBM.

``QTensor``/``Q4Tensor`` are registered pytree nodes, so quantized
weights slot into the existing stacked-layer pytrees — ``lax.scan``
slices the children along the layer axis exactly like plain arrays,
and sharding specs apply per child (parallel/sharding.quantized_specs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class QTensor:
    """int8 weights + per-output-channel fp scales for (..., in, out)."""

    def __init__(self, q: jnp.ndarray, scale: jnp.ndarray):
        self.q = q  # int8, (..., in, out)
        self.scale = scale  # fp32, (..., 1, out)

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


def quantize_tensor(w: jnp.ndarray) -> QTensor:
    """Per-output-channel symmetric int8 over the contraction (-2) axis."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)  # (..., 1, out)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QTensor(q, scale)


@jax.tree_util.register_pytree_node_class
class Q4Tensor:
    """Packed int4 weights + group-wise fp scales.

    q: int8 (..., in/2, out) — two nibbles per byte along the
    contraction axis (even row = low nibble, odd = high).
    scale: fp32 (..., n_groups, 1, out). The group size is derivable
    (in = 2·q.shape[-2]; group = in / n_groups), so no static aux data.
    """

    def __init__(self, q: jnp.ndarray, scale: jnp.ndarray):
        self.q = q
        self.scale = scale

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def quantize_tensor_int4(w: jnp.ndarray, group: int = 128) -> Q4Tensor:
    """Group-wise symmetric int4 ([-8, 7]) over the contraction axis."""
    wf = w.astype(jnp.float32)
    cin = wf.shape[-2]
    group = min(group, cin)
    assert cin % group == 0 and cin % 2 == 0, (cin, group)
    G = cin // group
    lead = wf.shape[:-2]
    out = wf.shape[-1]
    wg = wf.reshape(*lead, G, group, out)
    amax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)  # (..., G, 1, out)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(wg / scale), -8, 7).astype(jnp.int8).reshape(*lead, cin, out)
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    packed = ((lo & 0x0F) | ((hi & 0x0F) << 4)).astype(jnp.int8)
    return Q4Tensor(packed, scale)


def _dequant4(w: Q4Tensor, dtype) -> jnp.ndarray:
    """Unpack + rescale to a full weight; the whole chain is elementwise
    on the packed operand, so XLA fuses it into the consuming matmul."""
    p = w.q
    lead = p.shape[:-2]
    half, out = p.shape[-2], p.shape[-1]
    cin = 2 * half
    G = w.scale.shape[-3]
    # Arithmetic shifts on int8 sign-extend: low nibble via <<4 then >>4.
    lo = ((p << 4) >> 4).astype(jnp.int8)
    hi = (p >> 4).astype(jnp.int8)
    q = jnp.stack([lo, hi], axis=-2)  # (..., in/2, 2, out)
    q = q.reshape(*lead, G, cin // G, out)
    wf = q.astype(dtype) * w.scale.astype(dtype)
    return wf.reshape(*lead, cin, out)


def qmatmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for plain arrays, QTensors, or Q4Tensors (dequant fused)."""
    if isinstance(w, QTensor):
        y = x @ w.q.astype(x.dtype)
        return y * w.scale.astype(x.dtype)
    if isinstance(w, Q4Tensor):
        return x @ _dequant4(w, x.dtype)
    return x @ w


def qeinsum(eq: str, x: jnp.ndarray, w, out_dtype=None) -> jnp.ndarray:
    """einsum(eq, x, w) for plain arrays or QTensors, fp32 accumulation.

    The scale broadcast relies on per-output-channel scales keeping rank
    ((..., 1, out) vs weight (..., in, out)), which every einsum used by
    the MoE expert blocks preserves (contraction on the -2 axis)."""
    if isinstance(w, QTensor):
        y = jnp.einsum(eq, x, w.q.astype(x.dtype), preferred_element_type=jnp.float32)
        y = y * w.scale
    elif isinstance(w, Q4Tensor):
        y = jnp.einsum(eq, x, _dequant4(w, x.dtype), preferred_element_type=jnp.float32)
    else:
        y = jnp.einsum(eq, x, w, preferred_element_type=jnp.float32)
    return y.astype(out_dtype) if out_dtype is not None else y


# Weight names quantized in the decoder pytrees (matmul weights only —
# embeddings, norms, and routers stay full precision).
QUANTIZABLE = ("wq", "wk", "wv", "wo", "wg", "wu", "wd",
               # The sparse-attention indexer's query projection (models/mixtral.py,
               # ISSUE 36); its key and weight projections, (H, 64) and (H, 16), and
               # the norms stay full precision.
               "wq_idx",
               # Trinity-Mini's block (models/mixtral.py, ISSUE 41): the attention's
               # output gate, the shared expert, and the leading dense layers' stack
               # (``mixtral.PREFIX`` + a dense layer's matmul names).
               "attn_gate", "shared_wg", "shared_wu", "shared_wd",
               "dense_wq", "dense_wk", "dense_wv", "dense_wo", "dense_attn_gate",
               "dense_wg", "dense_wu", "dense_wd",
               # LFM2's gated short convolution (ISSUE 43): its input and output
               # projections; the taps stay full precision.
               "conv_in", "conv_out",
               # Granite-4.0-H's Mamba-2 mixer (ISSUE 50): its two projections; the
               # taps, their bias, the norm and the heads' A_log, D and dt_bias stay as they are.
               "in_proj", "out_proj",
               # Kimi-Linear's Kimi Delta Attention (ISSUE 54): the q/k/v and output
               # projections and the decay's and the gate's low-rank pairs; beta's
               # projection (H, heads), the taps, the norm, A_log and the biases stay as they are.
               "kda_qkv", "kda_out", "kda_fa", "kda_fb", "kda_ga", "kda_gb",
               # Xing4.0's latent attention (ISSUE 47): the two bottlenecks' down and
               # up projections, in both stacks; the hyper-connections' maps stay float32.
               "wdq", "wuq", "wdkv", "wukv", "dense_wdq", "dense_wuq", "dense_wdkv", "dense_wukv",
               # models/phi4flash.py's matmul leaves (its QUANT_LEAVES): the MLP,
               # the Mamba mixer's and the gated memory unit's projections, the
               # fused key/value projection. W_x, W_dt, the convolution, A_log, D,
               # the norms, lambdas and biases stay full precision.
               "fc1", "fc2", "m_in", "mix_out", "g_in", "wkv")


def quantize_llama_params(params: dict, mode: str = "int8", group: int = 128) -> dict:
    """Quantize the stacked layer matmuls of a llama/mixtral pytree.
    mode: "int8" (per-channel) or "int4" (group-wise packed)."""
    quant = quantize_tensor if mode == "int8" else (
        lambda w: quantize_tensor_int4(w, group))
    out = dict(params)
    layers = dict(params["layers"])
    for name in QUANTIZABLE:
        if name in layers:
            layers[name] = quant(layers[name])
    out["layers"] = layers
    if "lm_head" in out:
        out["lm_head"] = quant(out["lm_head"])
    if "mtp" in out:   # the next-token module (ISSUE 56): its block's matmuls and W_eh
        block = {n: quant(w) if n in QUANTIZABLE else w for n, w in out["mtp"]["layers"].items()}
        out["mtp"] = {**out["mtp"], "layers": block, "eh_proj": quant(out["mtp"]["eh_proj"])}
    return out


def seeded_std(cfg, leaf: str) -> float:
    """Scale of a seeded build's ``leaf``: 0.02, except in a mixture the
    token embedding (1.0), the attention's output projection (0.005) and
    the router (0.06), and in a model with a sparse-attention indexer the
    indexer's projections, the values and the output projection (each
    its fan-in ** -0.5: the comment in the body).

    Embedding and output projection, so that the routers read states as
    different as the tokens are: at 0.02 throughout, the map (what all
    tokens share) -> Wv -> Wo has gain 1.2, attention over seeded weights
    feeds the shared part back larger in every layer, the residual stream
    is soon nearly one vector for every token, and a decode step's 48
    rows x 6 choices fell on ~30 of SmallThinker's 64 experts a layer:
    half the expert bytes a trained, load-balanced router reads (40 with
    the embedding alone at 1.0, 62 with both; my chip runs, PR 28).

    The router, so that a choice that flips matters little: served in
    bf16, a sixth and a seventh logit closer than the rounding noise swap
    places against a float32 reference, and at 0.02 the sixth expert's
    weight is a tenth of the layer's output (one run in seven then read a
    logprob 0.51 nats off, past the benchmark's 0.45). At 0.06 the chosen
    six weigh about .61 .18 .09 .06 .04 .03: which experts are chosen, and
    so every byte and operation, is the same.

    An untied head is assumed (tied, unit-scale embeddings would give
    logits of scale 50).

    A model with Mamba layers (models/phi4flash.py): the embedding, which
    is also the head, at 0.02 (logits of unit scale at the published 2,560;
    a normed stream forgets the embedding's own scale); every other normal
    leaf at hidden_size ** -0.5, which is 0.0198 at the published width and
    keeps the tiny test preset (64 wide: 0.125) in the same regime: a
    projection of a normed stream has unit gain, B and C are O(1) so the
    state is a fifth of a mixer's output
    and a stale or rounded one shows in the logits (at 0.02 the 64-wide
    preset's state was under 1% of it and no test could see it), and W_dt d
    spreads by ~0.1 around the published b_dt, so the steps stay
    log-uniform in about [0.001, 0.1] and a state remembers tens to
    thousands of tokens. Two leaves keep their published spread: the
    convolution's taps 0.29 (uniform(-1/2, 1/2) of four taps) and the
    lambdas 0.1 (normal(0, 0.1)). A_log, D and b_dt are not normal at all:
    the model's initialiser writes them.

    A mixture with convolution layers (ISSUE 43): ``_lfm2_std``."""
    if hasattr(cfg, "attn_layer_period"):
        return _jamba_std(cfg, leaf)
    if hasattr(cfg, "mamba_d_state"):
        return {"m_conv_w": 0.29, "lam": 0.1, "embed": 0.02}.get(leaf, cfg.hidden_size ** -0.5)
    if getattr(cfg, "indexer_topk", 0):
        # A model whose attention reads only the tokens an indexer chooses
        # (ISSUE 36) is seeded so that WHICH tokens it reads shows in the
        # logits: a comparison of served logprobs with a reference is then a
        # comparison of the chosen sets too. Each of these leaves reads a
        # normed stream, so fan_in ** -0.5 has unit gain at the published
        # width and at the test preset's alike:
        # - the indexer's projections (0.022 at 2,048, 0.125 at 64): a head's
        #   query is O(1), q . k over the LayerNormed key spreads by
        #   sqrt(head_dim), half the products pass the relu and a row's scores
        #   differ by far more than bf16 rounds, so the set is the same served
        #   in bf16 and computed in float32 for all but the scores nearest the cut;
        # - values, and the output projection at (heads x head size) ** -0.5
        #   (0.0156 at 4,096): attention adds as much as the stream holds,
        #   where a mixture's 0.005 below keeps it under 1%;
        # - with them the q and k norms' gains (``seeded_qk_gain``).
        # With attention at 0.005 and gains of 1, a reference that attends over
        # EVERY key in place of the 2,048 chosen read 0.10 nats from the one
        # that selects (sandbox, float32, a 3,050-token probe): correct by the
        # benchmark's 0.45. Seeded like this it reads 1.02 on the chip (PERF.md
        # section 6, PR 36). The routers still read states as different as the
        # tokens are: a head that rests on few keys hands each token other
        # tokens' values, not the mean of all.
        fan_in = {"wq_idx": cfg.hidden_size, "wk_idx": cfg.hidden_size, "ww_idx": cfg.hidden_size,
                  "wv": cfg.hidden_size, "wo": cfg.num_heads * cfg.hd}.get(leaf)
        if fan_in:
            return fan_in ** -0.5
    if getattr(cfg, "kda", False):
        return _kimi_std(cfg, leaf)
    if getattr(cfg, "mamba2", False):
        return _granite_std(cfg, leaf)
    if getattr(cfg, "layer_conv", None) is not None:
        return _lfm2_std(cfg, leaf)
    if getattr(cfg, "kv_lora_rank", 0) and getattr(cfg, "sandwich_norm", False):
        return _pangu_std(cfg, leaf)
    if getattr(cfg, "kv_lora_rank", 0):
        return _xing_std(cfg, leaf)
    if getattr(cfg, "sandwich_norm", False):
        return _sandwich_std(cfg, leaf)
    if getattr(cfg, "num_experts", 0):
        return {"embed": 1.0, "wo": 0.005, "router": 0.06}.get(leaf, 0.02)
    if getattr(cfg, "eva_window", None):
        return _eva_std(cfg, leaf)
    return 0.02


def _jamba_std(cfg, leaf: str) -> float:
    """Jamba's seeded build (ISSUE 64; models/jamba.py): the hybrid's regime above
    (every matrix at hidden ** -0.5, the taps at their published 0.29), but for
    two things the benchmark's comparison needs. The tied embedding at 2 x hidden
    ** -0.5, so that the logits spread by 2 at the published width and at the
    64-wide test preset alike (at 0.02 they spread by 1.0 and by 0.16: the limits
    are in nats of a served token's logprob, so the spread sets what an error in
    the stream is worth there, ``_eva_std``; at 3 a sound run on the chip read a
    mean of 0.087 nats against the limit of 0.12, too near it: PERF.md section 6,
    PR 64). And the gains of the B and C norms
    at ``b_norm`` = ``c_norm`` = 2: with D = 1 and B, C of unit size the state's
    part S C of a mixer's output is a fifth of the skip D c beside it (the
    hybrid's note above); at 2 x 2 it is four fifths, as a trained mixer's
    dominates, and a state that is stale, lost at a chunk's cut, kept in
    bfloat16 or built from B and C that were never normed shows in the logits.
    The gains are leaves of ones in the published initialisation; a seeded gain
    that is not 1 also makes a norm left out another model."""
    return {"m_conv_w": 0.29, "embed": 2.0 * cfg.hidden_size ** -0.5, "b_norm": 2.0, "c_norm": 2.0,
            "dt_norm": 1.0}.get(leaf, cfg.hidden_size ** -0.5)


def _eva_std(cfg, leaf: str) -> float:
    """EvaByte's seeded build (ISSUE 61): every matrix at unit gain (fan-in ** -0.5,
    so the 64-wide test preset and the published 4,096 are in one regime: scores
    q . k / sqrt(D) spread by about one), the embedding of unit scale, and the head
    at three times unit gain: logits that spread by 3. The benchmark's limits are in
    nats of a served token's logprob, so the logits' spread sets what a given error
    in the stream is worth there: at a spread of 1 the reference with its matrices
    rounded to e4m3 still read ``correct`` (0.31 / 0.087 nats against limits of
    0.45 / 0.12; a sound run 0.03-0.05 / 0.010-0.024: PERF.md section 6, PR 61)."""
    if leaf == "embed":
        return 1.0
    gain = 3.0 if leaf == "lm_head" else 1.0
    return gain * {"wo": cfg.num_heads * cfg.hd, "wd": cfg.intermediate_size}.get(leaf, cfg.hidden_size) ** -0.5


def _sandwich_std(cfg, leaf: str) -> float:
    """``seeded_std`` for a model whose sublayers' OUTPUTS are normed
    (ISSUE 41, Trinity-Mini's block). Every sublayer then adds a vector of
    RMS 1 (the post-norm's gain) whatever its matrices' scales, so the
    scales that matter are those in front of a nonlinearity and the SHARES
    inside a sublayer, each given per fan-in so that the 64-wide test preset
    and the published 2,048 are in one regime:

    - the embedding at hidden ** -0.5: the model multiplies it by
      sqrt(hidden), so the stream starts at RMS 1 and each of the sixteen
      sublayers of a stage of eight is a seventeenth of what the head reads.
      (At the mixtures' 1.0 it would start at RMS 45 and no sublayer would
      show in a logit.)
    - the head at 2 x hidden ** -0.5: logits that spread by 2. The scale of
      every difference below follows it; at 2 a sound run reads a fifth to a
      third of the benchmark's limits and the thinnest control twice them
      (at 1.25 the chip read 0.096 / 0.025 sound and 0.43 / 0.147 for the bias
      dropped from the selection: PERF.md section 6, PR 41).
    - the attention's output gate at 2 x hidden ** -0.5: its logits spread
      by 2, the gate runs over 0.1-0.9, and an output that is not gated is
      another vector (cosine 0.85).
    - the router at hidden ** -0.5: logits spread by 1, sigmoid scores over
      0.3-0.7 with the chosen eight of 128 at 0.8-0.9, within a factor two
      of each other: the eighth weighs an eighth of the routed sum whatever
      the router's scale.
    - the selection bias at 0.04, against a spacing of 0.01 between the
      scores nearest the cut of 128 experts: it changes 1.8 of a token's
      eight and sends no token to a fixed eight (at 0.06 a 544-token step read
      116-123 of 128 experts a layer where an unbiased router reads 126-128,
      and 32 rows x 8 choices 70-84 for 84-95: sandbox, float32; independent
      tokens would read 111, the stream's common component costs the rest).
    - **the shared expert's down projection at 4 x 0.02**, so that the routed
      sum is a quarter of the sublayer's output and one expert swapped is a
      tenth of it (0.5 / sqrt(17)). With seeded weights the experts are
      independent random functions, and routing over them is CHAOTIC where a
      swap weighs more: bf16's rounding of the stream swaps one expert in
      ~3% of the (token, layer) pairs; at equal shares (a swap = 0.35 of the
      sublayer) that perturbation swaps two more experts in the next expert
      layer, three in the one after, and 6% of the tokens end 0.3-0.9 nats
      from a float32 reference (the benchmark's limit is 0.45; measured 0.44
      on the chip and 0.91 in the sandbox, PERF.md section 6, PR 41). At a
      quarter the cascade dies out (largest 0.16-0.29 over 96 tokens), and a
      reference that drops the bias from the selection, which swaps about
      two experts in EVERY token of every layer, still reads 3.3 times the
      sound one's mean in the sandbox and 6 times on the chip. A trained mixture is smooth across a routing boundary;
      seeded experts are not, so their share has to carry it.
    - every other matrix at 0.02; the q and k norms' gains at 1.4
      (``seeded_qk_gain``): a head rests on a few dozen keys, so which keys
      it sees (a window, a rotation) shows.
    """
    fan_in = cfg.hidden_size ** -0.5
    return {"embed": fan_in, "lm_head": 2 * fan_in, "attn_gate": 2 * fan_in, "router": fan_in,
            "router_bias": 0.04, "shared_wd": 0.08}.get(leaf, 0.02)


def _lfm2_std(cfg, leaf: str) -> float:
    """``seeded_std`` for LFM2's block (ISSUE 43): gated short convolutions
    beside attention layers on an UN-normed stream, a tied head, a sigmoid
    router whose routed sum is the whole FFN sublayer (no shared expert, no
    post-norm). Every sublayer reads a normed stream, so each scale is given
    per fan-in and the 64-wide test preset and the published 2,048 are in one
    regime; what matters is each sublayer's SIZE against the stream it joins.

    - the embedding, which is also the head, at 1.5 x hidden ** -0.5: logits
      that spread by 1.5. Every difference below was read at a spread of 2 and
      follows it (the chip read three quarters of them at 1.5): at 2 a sound
      run's largest difference was 0.18-0.28 nats over five runs on the chip,
      mean 0.053-0.073, under limits of 0.45 / 0.12 that one failing run in
      dozens must not pass; at 1.5 eleven runs read 0.11-0.27 / 0.042-0.070. The
      stream starts at that size and forgets it: every sublayer below adds
      0.1 to 2, so a token's own embedding is a hundredth of what the head
      reads (measured at the test preset: embedding 0.19, a conv operator 0.5,
      attention 0.7, a dense MLP 2.5, the stream 3.6-4.0 behind them) and the
      tied head does not vote for the token it was given.
    - the two leading dense MLPs' down projection at 4.4 x width ** -0.5
      (gate and up at hidden ** -0.5): their outputs, RMS ~2 each, set the
      stream's size ONCE (~3), so that it is nearly the same in front of all
      22 routers and one expert share serves every depth.
    - the convolution: ``W_in`` at hidden ** -0.5, so B, C and u are O(1) and
      their cubic product's spread stays near the stream's (at larger gates
      the product's heavy tail is what bf16 rounds); the three taps ALIKE at
      3 ** -0.5, so the two lagged taps are two thirds of the convolution and
      a fault in the carried tail shows (zeroed they read 6.6-8.7 / 3.2-4.1
      nats at either size; the limits are 0.45 / 0.12); ``W_out`` at 0.5 x
      hidden ** -0.5: a conv operator adds ~0.5, a sixth of the stream.
    - attention: values at hidden ** -0.5, the output projection at 1.5 x
      (heads x head size) ** -0.5, the q and k norms' gains at 1.4
      (``seeded_qk_gain``: a head rests on a few dozen keys, so the norms
      show: dropped they read 1.2-1.6 / 0.36-0.44).
    - the router at hidden ** -0.5 (logits spread by 1) and the selection
      bias at 2 / experts (0.0625 at 32 experts, 0.25 at the test preset's
      8): sized against the spacing of neighbouring scores, ~1 / experts, so
      it changes 0.9 of a token's 4 (of 2) at either size and starves no
      expert: 64 rows x 4 choices still read 31.3 of 32 (independent tokens;
      at 0.08 they read 30.0, at 0.12 28.3).
    - **the experts' down projection at 0.6 x width ** -0.5** (gate and up at
      hidden ** -0.5): the routed sum adds ~0.2 (top-4 of experts of RMS 0.36;
      0.25 at the test preset's top-2), a twentieth of the stream.
      Seeded experts are independent random functions, so a router score
      that bf16 rounds across the cut swaps in another function: the served
      stream is rounded to bf16 after every one of 48 sublayers, ~0.5% of
      noise at a router's input against a mean gap of 0.15 logit spreads
      between a token's 4th and 5th score, so ~4% of the (token, layer) pairs
      swap one expert whatever the experts' size, and a swap moves every
      later router by (swap / stream). What is free is that ratio. Measured in
      the sandbox at the published widths (bf16 / int8 ``forward`` against
      the float32 reference, teacher-forced, 96 tokens x 2 prompts, largest /
      mean nats): experts at 0.02 (0.85 x width ** -0.5) sound 0.28-0.32 /
      0.066-0.076, bias dropped from the selection 0.85-0.87 / 0.23-0.28;
      at 0.01 sound 0.23 / 0.044-0.048, bias dropped 0.41-0.44 / 0.115-0.116:
      the sound reading is 0.02 + 2.5 x std, the control's 12.6 x std, both
      linear, so their ratio only rises with the share (2.5 at 0.01, 3.6 at
      0.02) and no share puts both a factor two from the limits. At 0.6 x
      width ** -0.5 (0.0142) with gate and up at 0.0221: sound 0.28 / 0.069,
      the control 0.63 / 0.23, and the chip reads the same (0.18-0.28 /
      0.053-0.073 and 0.60-1.04 / 0.168-0.198; at the final spread of 1.5
      0.11-0.27 / 0.042-0.070 and 0.45-0.51 / 0.171-0.184: PERF.md section
      6, PR 43). At the test preset (top-2 of 8: a swap is HALF the routed sum)
      the same rule reads sound 0.06-0.28 / 0.023-0.057 and the control
      0.55-1.2 / 0.17-0.27 over five pairs of prompts; at 1.15 times it one
      pair in twelve read 0.53 sound.
    - every other matrix 0.02, norms 1."""
    fan = cfg.hidden_size ** -0.5
    return {"embed": 1.5 * fan, "router": fan, "router_bias": 2.0 / cfg.num_experts,
            "conv_in": fan, "conv_w": 3 ** -0.5, "conv_out": 0.5 * fan,
            "wv": fan, "wo": 1.5 * (cfg.num_heads * cfg.hd) ** -0.5,
            "wg": fan, "wu": fan, "wd": 0.6 * cfg.intermediate_size ** -0.5,
            "dense_wg": fan, "dense_wu": fan, "dense_wd": 4.4 * cfg.dense_intermediate_size ** -0.5}.get(leaf, 0.02)


def _xing_std(cfg, leaf: str) -> float:
    """``seeded_std`` for Xing4.0's block (ISSUE 47): a latent attention and
    hyper-connected streams around a dense prefix, a sigmoid router and a
    shared expert, an untied head on an UN-normed stream. ``_lfm2_std``'s
    regime, each scale per fan-in so that the test preset and the published
    3,584 are in one regime; what matters is each block's SIZE against the
    streams it joins, and that each new mechanism shows in a logit.

    - the embedding at 1.0 (every stream starts as it) and the head at 1.25 x
      hidden ** -0.5: logits that spread by 1.25 (at 1.5 and experts twice as
      large the first run on the chip read 0.43 / 0.078 nats against limits of
      0.45 / 0.12: PERF.md section 6, PR 47).
    - the latent attention: every projection at fan_in ** -0.5, so c_q, the
      latent (both normed), q, k_nope, k_rope and v are O(1); a score is then a
      sum of 192 unit products over sqrt(192), times YaRN's m * m = 2.0: a
      spread of 2, a head that rests on a few dozen keys (``seeded_qk_gain``'s
      regime), a third of it from the 64 rotary dims: dropped, or rotated
      wrongly, they move every head. The output projection at 1.5 x (heads x
      v_head_dim) ** -0.5: attention adds ~0.5 a layer.
    - the two leading dense MLPs' down projection at 4.4 x width ** -0.5 (gate
      and up at hidden ** -0.5): RMS ~2 each, they set the streams' size ONCE
      (~3) in front of all the routers.
    - the router at hidden ** -0.5 (logits spread by 1), the selection bias at
      2 / experts; the experts' down projection at 0.3 x width ** -0.5 (half
      ``_lfm2_std``'s, because the chosen weights sum to route_scale 2 and not
      to 1) and the shared expert's at 2 x width ** -0.5: the routed sum is
      ~0.12, and ONE expert swapped by bfloat16's rounding of a score at the
      cut moves the stream by a fortieth, as it does there, and does not
      cascade; the shared expert adds ~0.8.
    - the hyper-connections: phi at (streams x hidden) ** -0.5, so each map's
      dynamic logit spreads by 1 under alpha 1; the biases normal(0, 1): H_pre
      0.15-0.85, H_post 0.3-1.7 a stream a layer, H_res the Sinkhorn projection
      of exp(N(0, 1.4)): far from the identity and from 1/n, so the streams
      differ in how much of each earlier block they hold and what a block reads
      is another mix of them than their sum."""
    fan = cfg.hidden_size ** -0.5
    return {"embed": 1.0, "lm_head": 1.25 * fan, "router": fan, "router_bias": 2.0 / cfg.num_experts,
            "wdq": fan, "wuq": cfg.q_lora_rank ** -0.5, "wdkv": fan, "wukv": cfg.kv_lora_rank ** -0.5,
            "wo": 1.5 * (cfg.num_heads * cfg.v_head_dim) ** -0.5,
            "wg": fan, "wu": fan, "wd": 0.3 * cfg.intermediate_size ** -0.5,
            "shared_wg": fan, "shared_wu": fan, "shared_wd": 2.0 * (cfg.shared_experts * cfg.intermediate_size) ** -0.5,
            "dense_wg": fan, "dense_wu": fan, "dense_wd": 4.4 * cfg.dense_intermediate_size ** -0.5,
            "hc_phi": (max(cfg.hc_mult, 1) * cfg.hidden_size) ** -0.5, "hc_bias": 1.0}[leaf]


def _granite_std(cfg, leaf: str) -> float:
    """``seeded_std`` for Granite-4.0-H's block (ISSUE 50): Mamba-2 mixers
    beside one NoPE attention layer on an UN-normed stream whose sublayers join
    it times residual_multiplier r, a tied head over logits_scaling, a softmax
    router beside a shared MLP. ``_lfm2_std``'s regime: each scale per fan-in
    and per multiplier, so the test preset and the published 4,096 are one
    regime; what matters is each sublayer's SIZE against the stream it joins,
    and that each mechanism shows in a logit.

    - the embedding, which is also the head, at 1.5 x logits_scaling x hidden **
      -0.5: logits that spread by 1.5. The stream starts at s0 =
      embedding_multiplier x that (4.5 at the published sizes).
    - every sublayer adds c = s0 x 0.6 x sqrt(hidden / (2 x layers)): its last
      matrix is sized by ``out`` = c / r over what it reads. The TIED head votes
      for the token it was given with 1.5 x sqrt(hidden) x (the embedding's
      share of the last stream's RMS): coherent, so it grows with the width
      where everything else is random. At this c the share is 1.67 / sqrt(hidden)
      (a fortieth at 4,096) and the vote 2.5, a token among the likely ones and
      not the only one (at c = 0.3 x s0 the vote was 8 at the test preset and a
      greedy row repeated one token at -0.3 nats whatever the layers did: every
      control read under the limits). Not smaller: r and embedding_multiplier
      show ONLY in that share (every sublayer reads a normed stream), so r
      dropped (1 for 0.22) takes the vote from 2.5 to 0.6.
    - the mixer: W_in at hidden ** -0.5 (z, u and dt O(1)); the taps normal at
      K ** -0.5 (three quarters of the convolution is its carried tail), B's and
      C's times ``conv_bc_gain`` 2, so that what the STATE holds (C_t . B_s over
      128 columns, mostly of one sign behind the SiLU) outweighs the D x skip
      as in a trained model: a state lost between two steps moves the next
      tokens' outputs by their own size. W_out reads a normed vector: out x Di
      ** -0.5.
    - attention: q and k at sqrt(2 / (attention_multiplier x sqrt(head_dim))) x
      hidden ** -0.5, scores that spread by 2 (``seeded_qk_gain``'s regime: a
      head rests on a few dozen keys); values at hidden ** -0.5; the output
      projection at 3 x out x (heads x head_dim) ** -0.5 (a head's mean over a
      few dozen values is a third of one).
    - the router at hidden ** -0.5 (logits spread by 1: the ten chosen weigh
      .25 .. .04); experts' and the shared MLP's gate and up at hidden ** -0.5
      (a product of RMS 0.6). The shared MLP's down projection so that it adds
      c; the experts' so that the routed sum (weights' squares sum to ~0.13 at
      top-10, 0.38 at the test preset's top-3) adds 0.15 x c: an expert swapped
      by bfloat16's rounding of a logit at the cut then moves the stream by a
      hundredth (at 0.5 x c the test preset's third expert of eight moved it by a
      twentieth, and served logprobs read up to 1.3 nats from the reference)."""
    fan = cfg.hidden_size ** -0.5
    embed = 1.5 * cfg.logits_scaling * fan
    out = (0.6 * (cfg.hidden_size / (2 * cfg.num_layers)) ** 0.5 * cfg.embedding_multiplier * embed
           / cfg.residual_multiplier)
    multiplier = cfg.attention_multiplier or cfg.hd ** -0.5
    qk = (2.0 / (multiplier * cfg.hd ** 0.5)) ** 0.5 * fan
    return {"embed": embed, "in_proj": fan, "conv_w": cfg.conv_kernel ** -0.5, "conv_b": 0.1, "conv_bc_gain": 2.0,
            "out_proj": out * cfg.ssm_inner ** -0.5,
            "wq": qk, "wk": qk, "wv": fan, "wo": 3.0 * out * (cfg.num_heads * cfg.hd) ** -0.5,
            "router": fan, "wg": fan, "wu": fan, "wd": 0.15 * out / 0.22 * cfg.intermediate_size ** -0.5,
            "shared_wg": fan, "shared_wu": fan,
            "shared_wd": out / 0.6 * (cfg.shared_experts * cfg.intermediate_size) ** -0.5}[leaf]


def _kimi_std(cfg, leaf: str) -> float:
    """``seeded_std`` for Kimi-Linear's block (ISSUE 54): Kimi Delta Attention
    beside a latent attention with no rotary, one dense MLP in front, a sigmoid
    router over ALL the experts with a chip's share of them held, a shared
    expert, an untied head on an UN-normed stream. ``_xing_std``'s regime, each
    scale per fan-in so that the test preset and the published 2,304 are one
    regime; what matters is each block's SIZE against the stream it joins (it
    ends near 3.8), and that each mechanism shows in a logit.

    - the embedding at 1.0, the head at 1.25 x hidden ** -0.5 (``_xing_std``'s).
    - Kimi Delta Attention: W_qkv at hidden ** -0.5 and the taps normal at K **
      -0.5, so q, k and v are O(1) behind the SiLU, whose positive mean makes a
      head's keys ALIKE (a cosine of ~0.1 beyond chance): the delta rule's
      correction, S'^T k_t against v_t, is then of v_t's own size once a head
      remembers a hundred tokens, and dropped it moves every output. The
      decay's low-rank pair at hidden ** -0.5 and 0.5 x rank ** -0.5: a token
      moves a channel's step by e^+-0.5 around ``kda_heads_init``'s, which
      spreads a head's channels over [0.001, 0.1] x A, so one decay a head is
      another model. beta's projection and the gate's pair at fan-in ** -0.5
      (both sigmoids read N(0, 1)). What W_o reads is a normed head under a gate
      of ~0.5: at 1.5 x P ** -0.5 the layer adds ~0.8, all of it read from the
      STATE (a state lost shows whole).
    - the latent attention: ``_xing_std``'s, with the queries at 2 x hidden **
      -0.5: no YaRN doubles the scores here, and without position encoding a
      head that weighs thousands of keys alike adds their mean (a spread of 2
      rests it on a few dozen).
    - the leading dense MLP's down projection at 4.4 x width ** -0.5 (RMS ~2, in
      front of every router), the shared expert's at 2 x width ** -0.5 (~0.8).
    - the router at hidden ** -0.5, the selection bias at 2 / experts; the
      experts' down projection at 0.77 x width ** -0.5: the chosen eight weigh
      0.31 each, four of them held here on average, so the held half's sum is
      ~0.28 and the ABSENT half's the same: computed in place of left out it
      moves the last stream by a fifth over seven layers (the control's mean,
      0.2 nats, against a limit of 0.12), while one expert swapped by
      bfloat16's rounding of a score at the cut moves it by a twenty-fifth
      (between ``_xing_std``'s fortieth and the twentieth that read 0.43 there)."""
    fan = cfg.hidden_size ** -0.5
    rank = cfg.kda_rank or cfg.kda_head_dim
    return {"embed": 1.0, "lm_head": 1.25 * fan, "router": fan, "router_bias": 2.0 / cfg.num_experts,
            "kda_qkv": fan, "conv_w": cfg.conv_kernel ** -0.5, "kda_fa": fan, "kda_fb": 0.5 * rank ** -0.5,
            "kda_b": fan, "kda_ga": fan, "kda_gb": rank ** -0.5, "kda_out": 1.5 * cfg.kda_inner ** -0.5,
            "wq": 2.0 * fan, "wdkv": fan, "wukv": cfg.kv_lora_rank ** -0.5,
            "wo": 1.5 * (cfg.num_heads * cfg.v_head_dim) ** -0.5,
            "wg": fan, "wu": fan, "wd": 0.77 * cfg.intermediate_size ** -0.5,
            "shared_wg": fan, "shared_wu": fan, "shared_wd": 2.0 * (cfg.shared_experts * cfg.intermediate_size) ** -0.5,
            "dense_wg": fan, "dense_wu": fan, "dense_wd": 4.4 * cfg.dense_intermediate_size ** -0.5}[leaf]


def _pangu_std(cfg, leaf: str) -> float:
    """``seeded_std`` for openPangu-Ultra-MoE's block (ISSUE 56): a latent
    attention with a query bottleneck under rotary, every sublayer's OUTPUT
    normed, one dense MLP in front, a sigmoid router over ALL the experts with
    a sixteenth of them held, a shared expert, an untied head over a slice of
    the vocabulary, and the next-token module, whose block is seeded by these
    same rules. ``_xing_std``'s regime per fan-in, so the test preset and the
    published 7,680 are one regime; under the output norms what matters is the
    two GAINS and the shares inside a sublayer.

    - the embedding at 1.0, the head at 1.25 x hidden ** -0.5 (``_xing_std``'s).
    - the output norms' gains: 0.5 behind attention, 0.75 behind the FFN (a gain is
      what its sublayer ADDS, whatever its matrices' scales): the stream ends near
      2.6 behind seven layers, a layer's FFN under a third of it. Dropped,
      attention adds what W_O gives and the FFN ~1.1: other sizes, another model.
    - the latent attention: ``_xing_std``'s projections with the queries' up
      projection at 1.4 x rank ** -0.5: no YaRN doubles the scores here, and a
      spread of 2 rests a head on a few dozen keys, so a second query that reads
      the cache as the first does (one position short) is another output.
    - the router at hidden ** -0.5, the selection bias 0 (the config has none: a
      leaf of zeros); the experts' down projection at 1.4 x width ** -0.5 and the
      shared expert's at 2 x width ** -0.5: a token's eight chosen weigh 0.31
      each and add ~0.7 together beside the shared expert's ~0.8, of which this
      chip's sixteenth holds half an expert a token on average: the ABSENT
      experts computed in place of left out turn the FFN's normed output by
      about 40 degrees, while one held expert swapped by bfloat16's rounding of
      a score at the cut moves it by a twelfth, once in sixteen swaps.
    - W_eh at (2 x hidden) ** -0.5 x 1.4: both normed halves weigh alike and
      the module's stream starts at RMS ~1.4, under its block's two additions.
    No scale here was chosen for its effect on the drafts' acceptance."""
    fan = cfg.hidden_size ** -0.5
    return {"embed": 1.0, "lm_head": 1.25 * fan, "router": fan, "router_bias": 0.0,
            "post_attn_norm": 0.5, "post_ffn_norm": 0.75,
            "wdq": fan, "wuq": 1.4 * cfg.q_lora_rank ** -0.5, "wdkv": fan, "wukv": cfg.kv_lora_rank ** -0.5,
            "wo": 1.5 * (cfg.num_heads * cfg.v_head_dim) ** -0.5,
            "wg": fan, "wu": fan, "wd": 1.4 * cfg.intermediate_size ** -0.5,
            "shared_wg": fan, "shared_wu": fan, "shared_wd": 2.0 * (cfg.shared_experts * cfg.intermediate_size) ** -0.5,
            "dense_wg": fan, "dense_wu": fan, "dense_wd": 4.4 * cfg.dense_intermediate_size ** -0.5,
            "eh_proj": 1.4 * (2 * cfg.hidden_size) ** -0.5}[leaf]


def seeded_qk_gain(cfg) -> float:
    """What a seeded build writes into ``q_norm`` and ``k_norm``: 1, and 1.4
    for a model with a sparse-attention indexer and for one whose sublayers'
    outputs are normed (ISSUE 41: what attention adds is then a vector of RMS 1
    whichever keys it rests on, and only a head that rests on FEW keys makes a
    window or a missing rotation show). A normed query times a normed
    key over sqrt(head size) spreads by the product of the two gains whatever
    the head size: at 1 a seeded head weighs thousands of keys nearly alike
    and its output is their mean whichever quarter of them was chosen; at 1.4
    (a spread of 2) it rests on a few dozen of 2,048, as a trained head does,
    and a set that lacks them gives another output. Not higher: at 1.75 (a
    spread of 3, a head on one key or two) bf16's rounding of a score moves
    whole heads, and served logprobs read 0.27 and 0.52 nats from the float32
    reference over two runs on the chip (PERF.md section 6, PR 36), past the
    benchmark's limit; at 1.4 they read 0.07."""
    return 1.4 if (getattr(cfg, "indexer_topk", 0) or getattr(cfg, "sandwich_norm", False)
                   or getattr(cfg, "layer_conv", None) is not None) else 1.0


def init_quantized_llama_params(rng: jax.Array, cfg, mode: str = "int8",
                                group: int = 128, dtype=jnp.bfloat16) -> dict:
    """Random-init + quantize a llama tree WITHOUT materializing the
    full-precision weights.

    Each stacked matmul leaf is initialized and quantized one LAYER at
    a time (init→quantize fused in one jit, so the bf16 transient is a
    single 2-D matrix ≈100 MiB at 8B scale) and the per-layer results
    are restacked. Full bf16 init of Llama-3-8B needs ~16 GiB — more
    than a whole v5e chip — before quantization even starts; this path
    peaks at int4 weights (~4.7 GiB) + one layer's transient, which is
    what lets the committed single-chip profile `v5e-1-llama-3-8b-int4`
    (serving/profiles.py) build with random weights on one chip.

    Tree structure/dtypes exactly match quantize_llama_params(
    llama.init_params(...)); the random values differ (keys are
    folded per layer), which is irrelevant for perf benches.

    A mixture (``cfg.num_experts``; models/mixtral.py's tree) gets its
    expert leaves (L, E, in, out) the same way, one layer's E experts
    at a time, written into the stacked leaf in place (a whole expert
    leaf of SmallThinker's 16-layer stage is 8 GB in float32; a layer's
    block is 0.5 GB), and a full-precision router.
    """
    from functools import partial as _partial

    if hasattr(cfg, "mamba_d_state"):
        # Its tree is its own (models/phi4flash.py: stacked by pair, five kinds of
        # layer; models/jamba.py: three stacks): the model's initialiser builds
        # each matmul leaf quantised, an entry of the stack at a time, as
        # ``qstack`` does below.
        import sys

        if mode != "int8":
            raise ValueError(f"{type(cfg).__name__}: int8 is the one weight-only quantisation built for it")
        return sys.modules[type(cfg).__module__].init_params(rng, cfg, dtype=dtype, quant=quantize_tensor)

    # A mixture's leading dense layers are a stack of their own (ISSUE 41).
    L = getattr(cfg, "expert_layers", cfg.num_layers)
    H, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    keys = jax.random.split(rng, 8)
    quant = quantize_tensor if mode == "int8" else (
        lambda w: quantize_tensor_int4(w, group))

    def norm(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    @_partial(jax.jit, static_argnums=(1, 2))
    def qinit(key, shape, std=0.02):
        return quant(norm(key, shape, std))

    def qstack(key, shape, std=0.02):
        per = [qinit(jax.random.fold_in(key, layer), shape[1:], std) for layer in range(shape[0])]
        q = jnp.stack([p.q for p in per])
        scale = jnp.stack([p.scale for p in per])
        return type(per[0])(q, scale)

    @_partial(jax.jit, donate_argnums=(0,))
    def put(stacked, block, layer):
        return jax.tree.map(lambda s, b: jax.lax.dynamic_update_index_in_dim(s, b, layer, 0),
                            stacked, block)

    def qfill(key, shape, std=0.02):
        """qstack for a leaf too large to hold twice: the stacked leaf is
        allocated once and each layer's block is written into it."""
        block = jax.eval_shape(lambda: qinit(key, shape[1:], std))
        stacked = jax.tree.map(lambda b: jnp.zeros((shape[0],) + b.shape, b.dtype), block)
        for layer in range(shape[0]):
            stacked = put(stacked, qinit(jax.random.fold_in(key, layer), shape[1:], std), layer)
        return stacked

    E = getattr(cfg, "num_experts", 0)
    mtp = None
    if E:
        from inference_gateway_tpu.models import mixtral

        def mixture_layers(rng, L, lead=True):
            """A stack of ``L`` expert layers drawn from ``rng`` (with ``lead`` the leading
            dense layers' beside it), and what draws a further matrix from its stream."""
            keys = jax.random.split(rng, 8)
            Eh = cfg.held_experts   # the stacks hold a chip's share (ISSUE 54); the router is E wide

            # One key a leaf the older mixtures have, as they always drew them; the
            # leaves ISSUE 41 added draw from a stream of their own.
            named = {"wq": keys[1], "wk": keys[2], "wv": keys[3], "wo": keys[4]}
            more = iter(jax.random.split(jax.random.fold_in(rng, 41), 24))

            def matrix(name, shape):
                return qstack(named[name] if name in named else next(more), shape, seeded_std(cfg, name))

            def lead_matrix(name, shape):
                return qstack(next(more), shape, seeded_std(cfg, name))

            hc_keys = iter(jax.random.split(jax.random.fold_in(rng, 47), 8))

            def plain(name, shape):  # the hyper-connections' float32 leaves (ISSUE 47), a stream of their own
                return jax.random.normal(next(hc_keys), shape, jnp.float32) * seeded_std(cfg, name)

            ekeys = jax.random.split(jax.random.fold_in(rng, 7), 4)
            layers = {
                **mixtral.layer_leaves(cfg, L, dtype, matrix, plain),
                **mixtral.operator_leaves(cfg, dtype, matrix,
                                          lambda name, shape: norm(next(more), shape, seeded_std(cfg, name))),
                "moe_norm": jnp.ones((L, H), dtype),
                "router": norm(ekeys[0], (L, H, E), seeded_std(cfg, "router")),
                "wg": qfill(ekeys[1], (L, Eh, H, I), seeded_std(cfg, "wg")),
                "wu": qfill(ekeys[2], (L, Eh, H, I), seeded_std(cfg, "wu")),
                "wd": qfill(ekeys[3], (L, Eh, I, H), seeded_std(cfg, "wd")),
            }
            if cfg.indexer_topk:
                ikeys = jax.random.split(jax.random.fold_in(rng, 36), 3)
                Hi, Di, std = cfg.indexer_heads, cfg.indexer_head_dim, seeded_std(cfg, "wq_idx")
                layers.update(wq_idx=qstack(ikeys[0], (L, H, Hi * Di), std),
                              wk_idx=norm(ikeys[1], (L, H, Di), std),
                              ww_idx=norm(ikeys[2], (L, H, Hi), std),
                              k_idx_norm=jnp.ones((L, Di), dtype), k_idx_bias=jnp.zeros((L, Di), dtype))
            if cfg.router_score == "sigmoid":
                layers["router_bias"] = norm(next(more), (L, E), seeded_std(cfg, "router_bias"))
            if cfg.shared_experts:
                Is = cfg.shared_experts * I
                layers.update(shared_wg=matrix("shared_wg", (L, H, Is)), shared_wu=matrix("shared_wu", (L, H, Is)),
                              shared_wd=matrix("shared_wd", (L, Is, H)))
            if cfg.num_dense_layers and lead:
                Lp, Id = cfg.num_dense_layers, cfg.dense_intermediate_size
                first = {**mixtral.layer_leaves(cfg, Lp, dtype, lead_matrix, plain), "mlp_norm": jnp.ones((Lp, H), dtype),
                         "wg": lead_matrix("dense_wg", (Lp, H, Id)), "wu": lead_matrix("dense_wu", (Lp, H, Id)),
                         "wd": lead_matrix("dense_wd", (Lp, Id, H))}   # (sized by their own names: seeded_std)
                layers.update({mixtral.PREFIX + n: w for n, w in first.items()})
            return layers, lambda name, shape: qinit(next(more), shape, seeded_std(cfg, name))

        layers, _ = mixture_layers(rng, L)
        if getattr(cfg, "mtp_layers", 0):
            # The next-token module (ISSUE 56): one block seeded by the expert layers'
            # rule from a key of its own, its two norms, W_eh and its final norm.
            block, further = mixture_layers(jax.random.fold_in(rng, mixtral.MTP_KEY), cfg.mtp_layers, lead=False)
            mtp = {"enorm": jnp.ones((H,), dtype), "hnorm": jnp.ones((H,), dtype),
                   "eh_proj": further("eh_proj", (2 * H, H)), "layers": block, "final_norm": jnp.ones((H,), dtype)}
    else:
        # (0.02 where it always was, whatever ``seeded_std`` says of a leaf: an older preset's values stay)
        std = lambda leaf: seeded_std(cfg, leaf) if leaf in ("wv", "wo") or getattr(cfg, "eva_window", None) else 0.02
        one = jnp.zeros if getattr(cfg, "norm_offset", False) else jnp.ones   # (stored beside a unit offset: llama.init_params)
        layers = {
            "attn_norm": one((L, H), dtype),
            "wq": qstack(keys[1], (L, H, Hq * D), std("wq")),
            "wk": qstack(keys[2], (L, H, Hkv * D), std("wk")),
            "wv": qstack(keys[3], (L, H, Hkv * D), std("wv")),
            "wo": qstack(keys[4], (L, Hq * D, H), std("wo")),
            "mlp_norm": one((L, H), dtype),
            "wg": qstack(keys[5], (L, H, I), std("wg")),
            "wu": qstack(keys[6], (L, H, I), std("wu")),
            "wd": qstack(keys[7], (L, I, H), std("wd")),
        }
        if getattr(cfg, "eva_window", None):
            from inference_gateway_tpu.models.llama import eva_vectors

            layers.update(eva_vectors(jax.random.fold_in(rng, 61), cfg, dtype))
    params = {"embed": norm(keys[0], (V, H), seeded_std(cfg, "embed")), "layers": layers,
              "final_norm": (jnp.zeros if getattr(cfg, "norm_offset", False) and not E else jnp.ones)((H,), dtype)}
    if cfg.qkv_bias:
        params["layers"]["bq"] = jnp.zeros((L, Hq * D), dtype)
        params["layers"]["bk"] = jnp.zeros((L, Hkv * D), dtype)
        params["layers"]["bv"] = jnp.zeros((L, Hkv * D), dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = qinit(jax.random.fold_in(rng, 99), (H, V * getattr(cfg, "num_pred_heads", 1)),
                                  seeded_std(cfg, "lm_head"))
    if mtp is not None:
        params["mtp"] = mtp
    return params


def dequantize_error(w: jnp.ndarray, mode: str = "int8", group: int = 128) -> float:
    """Max relative reconstruction error (diagnostics)."""
    if mode == "int8":
        qt = quantize_tensor(w)
        back = qt.q.astype(jnp.float32) * qt.scale
    else:
        back = _dequant4(quantize_tensor_int4(w, group), jnp.float32)
    denom = jnp.maximum(jnp.abs(w.astype(jnp.float32)), 1e-8)
    return float(jnp.max(jnp.abs(back - w.astype(jnp.float32)) / denom))
