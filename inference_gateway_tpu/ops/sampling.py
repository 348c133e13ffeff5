"""On-device token sampling.

Vectorized over the batch with *per-row* temperature and top-p so a
continuous batch can mix greedy and sampled requests in one jitted decode
step (no per-request recompiles). top_k is a static cap applied before
top-p to bound the sort cost on the vocab axis.

The k candidates come from ``select_top_k``: an EXACT grouped top-k.
The k-th largest group maximum t is a lower bound of the k-th largest
element, every element above t lies in a group whose maximum is above t,
and the k groups with the largest maxima hold all of those and k elements
>= t. Ties go to the lower column id, as lax.top_k's do: groups are runs
of neighbouring columns, taken lowest id first and kept in column order,
so values and indices are lax.top_k's on every input and every sampled
stream is unchanged. The groups' width follows the static (V, k)
(``top_k_plan``: 128 columns for the large vocabularies, 64 or 32 under
512 groups of 128), and whatever axes lead the vocabulary's are selected
as rows of one two-axis operand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

GREEDY_EPS = 1e-4

# Additive bias for grammar-masked vocabulary entries (ISSUE 13): large
# enough that softmax assigns masked tokens exactly zero probability in
# fp32, finite so a defensively all-masked row (a dead automaton state
# decoded past a finish inside a fused chunk — the host discards those
# tokens) degrades to argmax of the raw logits instead of NaN.
MASK_NEG = -1e30


def packed_mask_bias(bits: jnp.ndarray, vocab_size: int) -> jnp.ndarray:
    """Expand packed V-bit allowed-token rows into an additive bias.

    bits (..., W) uint32 — bit v lives at word v // 32, position v % 32
    (structured/automaton.pack_mask). Returns (..., V) float32: 0 where
    the token is allowed, MASK_NEG where the grammar forbids it. Applied
    to logits BEFORE top-k/top-p so constrained rows keep exact nucleus
    semantics over the allowed set.
    """
    v = jnp.arange(vocab_size)
    words = jnp.take(bits, v // 32, axis=-1)
    allowed = (words >> (v % 32).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.where(allowed.astype(bool), 0.0, MASK_NEG)


def per_row_keys(
    rng: jax.Array,
    seeds: jnp.ndarray,  # (B,) int32 request seeds
    use_seed: jnp.ndarray,  # (B,) bool — row has an explicit seed
    positions: jnp.ndarray,  # (B,) generation positions
) -> jnp.ndarray:
    """Per-row PRNG keys: seeded rows derive from (seed, position) so the
    same request with the same seed reproduces its samples regardless of
    batch composition; unseeded rows derive from the step rng + row."""
    B = seeds.shape[0]
    seeded = jax.vmap(lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p))(seeds, positions)
    unseeded = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(B))
    return jnp.where(use_seed[:, None], seeded, unseeded)


def chunk_row_keys(
    rng: jax.Array,
    seeds: jnp.ndarray,  # (B,)
    use_seed: jnp.ndarray,  # (B,)
    positions: jnp.ndarray,  # (B,) positions BEFORE the chunk's first step
    n_steps: int,
) -> jnp.ndarray:
    """All (step, row) keys for a fused decode chunk in one batched
    derivation: bit-identical to per_row_keys(fold_in(rng, i), seeds,
    use_seed, positions + 1 + i) per step, but a single vectorized
    threefry dispatch. Round-3 v5e profiling measured ~0.56 ms/step of
    in-scan RNG/sampling overhead — many tiny key-derivation launches —
    which this hoists out of the decode loop."""
    B = seeds.shape[0]
    steps = jnp.arange(n_steps)
    seeded = jax.vmap(lambda i: jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p + 1 + i)
    )(seeds, positions))(steps)
    unseeded = jax.vmap(lambda i: jax.vmap(
        lambda b: jax.random.fold_in(jax.random.fold_in(rng, i), b)
    )(jnp.arange(B)))(steps)
    return jnp.where(use_seed[None, :, None], seeded, unseeded)  # (n, B, 2)


# The grouped selection's constants, from the operation alone on a v5e
# (hack/topk_bench.py, device time, k = 64). PERF.md §6, PR 31:
# [48, 152064] lax.top_k 1.43 ms, 128-wide groups 0.38 ms (the TopK call
# over the 8,192 candidates 0.22 of it, whatever the vocabulary), so
# 128-wide groups pay from 8 groups per candidate asked for (512) on.
# PERF.md §5-§6, PR 57, under that, by width 128 / 64 / 32 / 16 against
# lax.top_k: [128, 19200] 0.36 / 0.28 / 0.27 / 0.26 against 0.52 ms;
# [64, 19200] 0.30 / 0.21 / 0.18 / 0.17 against 0.43; [24, 32000] 0.26 /
# 0.088 / 0.081 / 0.099 against 0.215; [48, 32000] 0.29 / 0.18 / 0.14 /
# 0.22 against 0.43: up to 4,096 candidates are sorted in place and no
# TopK call is made, and 32 is the width both take by the same 8 groups
# per candidate. At 152,064 columns 32 loses to 128 (0.48 against 0.38).
# Three axes: lax.top_k over [64, 2, 19200] is a sort of every column,
# 8.65 ms; the same rows as [128, 19200] are a TopK call, and the grouped
# selection reads them through its first reduction at no cost (0.25 ms).
GROUP_WIDTHS = (128, 64, 32)
GROUPED_MIN_GROUPS_PER_K = 8


def top_k_plan(vocab_size: int, k: int) -> dict:
    """Which selection ``select_top_k`` takes for a (V, k): a function of
    the static shape alone. ``grouped`` at the widest of GROUP_WIDTHS at
    which the vocabulary holds enough groups for the second selection to
    be much smaller than the first; ``direct`` (plain lax.top_k)
    where none does, k >= V included."""
    if 0 < k < vocab_size:
        for width in GROUP_WIDTHS:
            groups = -(-vocab_size // width)
            if groups >= GROUPED_MIN_GROUPS_PER_K * k:
                return {"top_k": k, "path": "grouped", "group_width": width, "groups": groups,
                        "candidates": k * width}
    return {"top_k": k, "path": "direct", "group_width": 0, "groups": 0, "candidates": vocab_size}


def grouped_top_k(x: jnp.ndarray, k: int, g: int):
    """The exact top-k of (rows, V) through groups of ``g`` columns: one
    pass over ``x`` for the groups' maxima, then a selection among the
    k * g columns of the k groups with the largest."""
    rows, V = x.shape
    G = -(-V // g)
    if G * g != V:  # pad, never truncate: -inf at the highest ids loses every tie
        x = jnp.pad(x, [(0, 0), (0, G * g - V)], constant_values=-jnp.inf)
    xg = x.reshape(rows, G, g)
    _, gid = jax.lax.top_k(jnp.max(xg, axis=-1), k)  # the k groups with the largest maxima
    gid = jnp.sort(gid, axis=-1)  # ascending: candidate order is column order
    cand = jnp.take_along_axis(xg, gid[..., None], axis=-2)  # (rows, k, g)
    vals, pos = jax.lax.top_k(cand.reshape(rows, k * g), k)
    idx = jnp.take_along_axis(gid, pos // g, axis=-1) * g + pos % g
    return vals, idx


def select_top_k(x: jnp.ndarray, k: int):
    """``jax.lax.top_k(x, k)`` — the same values AND indices on every
    input — by the selection ``top_k_plan`` names for the shape. Leading
    axes are folded into rows first: every lax.top_k inside sees two axes
    (over three a TPU sorts the whole vocabulary)."""
    lead, V = x.shape[:-1], x.shape[-1]
    plan = top_k_plan(V, k)
    x = x.reshape(-1, V)
    if plan["path"] == "direct":
        vals, idx = jax.lax.top_k(x, k)
    else:
        vals, idx = grouped_top_k(x, k, plan["group_width"])
    return vals.reshape(*lead, k), idx.reshape(*lead, k)


def top_k_nucleus(scaled: jnp.ndarray, top_p: jnp.ndarray, top_k: int):
    """The one top-k + nucleus filter all samplers share: sort the k
    best (already-tempered) logits, drop everything outside the smallest
    prefix whose probability mass reaches top_p (always keeping the
    argmax). Returns (filtered_vals (..., k) with -inf outside the
    nucleus, idx (..., k))."""
    vals, idx = select_top_k(scaled, top_k)  # sorted desc
    probs = jax.nn.softmax(vals, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < top_p[..., None]
    keep = keep.at[..., 0].set(True)
    return jnp.where(keep, vals, -jnp.inf), idx


def effective_top_k(top_k: int, vocab_size: int) -> int:
    """The k actually sorted by the fused-decode sampling path: top_k=0
    ("disabled", see sample_tokens) and top_k >= vocab degrade to a
    full-vocab sort so nucleus semantics are preserved instead of
    crashing lax.top_k (code-review round 3)."""
    return top_k if 0 < top_k < vocab_size else vocab_size


def chunk_gumbels(keys: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """Gumbel noise for every (step, row) of a chunk, batched. Sampling
    with argmax(filtered + gumbel(key, (k,))) is bit-identical to
    jax.random.categorical(key, filtered) — categorical IS the gumbel
    trick — so hoisting the draws out of the scan changes nothing about
    the sampled streams."""
    return jax.vmap(jax.vmap(lambda k: jax.random.gumbel(k, (top_k,))))(keys)


def sample_tokens_pregumbel(
    logits: jnp.ndarray,  # (B, V) fp32
    temperature: jnp.ndarray,  # (B,)
    top_p: jnp.ndarray,  # (B,)
    gumbel: jnp.ndarray,  # (B, top_k) precomputed via chunk_gumbels
    top_k: int,
) -> jnp.ndarray:
    """sample_tokens' top-k fast path with the RNG hoisted out: only
    top_k + nucleus filter + argmax remain in the decode loop.

    Grammar masks and logit_bias (ISSUE 13) are additive-bias terms the
    engine folds into ``logits`` BEFORE this call (packed_mask_bias) —
    one application path, shared by greedy argmax, the filter, and the
    logprob computation."""
    logits = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, GREEDY_EPS)[:, None]
    filtered, idx = top_k_nucleus(logits / temp, top_p, top_k)
    sampled_in_k = jnp.argmax(filtered + gumbel, axis=-1)
    sampled_tok = jnp.take_along_axis(idx, sampled_in_k[:, None], axis=-1)[:, 0]
    return jnp.where(temperature <= GREEDY_EPS, greedy_tok, sampled_tok)


def sample_tokens(
    logits: jnp.ndarray,  # (B, V) fp32
    rng: jax.Array,
    temperature: jnp.ndarray,  # (B,)
    top_p: jnp.ndarray,  # (B,)
    top_k: int = 0,  # static; 0 = disabled
    row_keys: jnp.ndarray | None = None,  # (B, 2) per-row keys override rng
) -> jnp.ndarray:
    """Sample one token per row; temperature <= GREEDY_EPS means argmax.

    Grammar-constrained rows (ISSUE 13) arrive with packed_mask_bias
    (and any logit_bias row) already ADDED to ``logits`` — the additive
    −inf bias lands before the greedy argmax and the top-k/top-p filter,
    so constrained and unconstrained rows coexist in one batch."""
    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    greedy_tok = jnp.argmax(logits, axis=-1)

    temp = jnp.maximum(temperature, GREEDY_EPS)[:, None]
    scaled = logits / temp

    if top_k and top_k < V:
        # Fast path: lax.top_k returns the k candidates ALREADY sorted
        # descending, so the nucleus filter runs on a (B, k) strip and
        # the O(V log V) vocab argsort disappears. A full-vocab sort per
        # decode step was the single largest consumer of the serving
        # step budget on real v5e hardware (round-3 profiling: sorts
        # lower terribly on TPU; the whole 22-layer TinyLlama forward
        # was cheaper than one 32k-column argsort).
        filtered, idx = top_k_nucleus(scaled, top_p, top_k)
        if row_keys is None:
            sampled_in_k = jax.random.categorical(rng, filtered, axis=-1)
        else:
            sampled_in_k = jax.vmap(lambda k_, row: jax.random.categorical(k_, row))(
                row_keys, filtered)
        sampled_tok = jnp.take_along_axis(idx, sampled_in_k[:, None], axis=-1)[:, 0]
        return jnp.where(temperature <= GREEDY_EPS, greedy_tok, sampled_tok)

    # top-p (nucleus) over the full vocab (top_k disabled): keep the
    # smallest prefix of the sorted probs whose cumulative mass reaches
    # top_p; always keep the argmax.
    sort_idx = jnp.argsort(-scaled, axis=-1)
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    keep_sorted = cum - sorted_probs < top_p[:, None]
    keep_sorted = keep_sorted.at[:, 0].set(True)
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(B)[:, None], sort_idx
    ].set(keep_sorted)
    filtered = jnp.where(keep, scaled, -jnp.inf)

    if row_keys is None:
        sampled_tok = jax.random.categorical(rng, filtered, axis=-1)
    else:
        sampled_tok = jax.vmap(lambda k, row: jax.random.categorical(k, row))(row_keys, filtered)
    return jnp.where(temperature <= GREEDY_EPS, greedy_tok, sampled_tok)


def compute_logprobs(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Log-probability of chosen tokens: logits (B, V), tokens (B,)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
