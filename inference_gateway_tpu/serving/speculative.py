"""Speculative decoding: draft-model proposals, one-pass target verify.

A small draft model proposes K tokens autoregressively; the target model
scores all K+1 positions in ONE forward (the same weight-stream cost as
a single decode step), and standard speculative rejection sampling
accepts a prefix of the proposals plus one extra token — so each target
step emits between 1 and K+1 tokens with the target's exact sampling
distribution. References: Leviathan et al. 2023 (PAPERS.md); the
reference gateway has no counterpart (it performs no inference,
SURVEY.md §6) — this is serving-stack surface introduced by the TPU
rebuild.

TPU-first shape discipline (everything here is trace-static):

- All distributions live on the top-k STRIP (the (k,) filtered+
  renormalized probs + their vocab indices) — never a (V,) tensor per
  draft step. Acceptance ratios, residual distributions, and resampling
  are strip algebra: O(K·k) per slot, not O(K·V).
- The draft catch-up block is provably ≤ 2 tokens (the draft prefills
  alongside the target at admission, and each round leaves the draft at
  most [rejected-extra] or [d_K, bonus] behind), so every round has the
  same static shapes: no bucketing, one compiled program.
- Greedy rows (temperature ≤ GREEDY_EPS) are EXACTLY the target's
  argmax stream: the filtered strip at eps-temperature is one-hot, the
  ratio test accepts iff draft == target argmax, the residual collapses
  to the target argmax, and explicit argmax overrides break float ties
  the same way the non-speculative path does. test_speculative.py pins
  greedy spec == greedy non-spec token-for-token.

Distribution note: sampled rows are rejection-sampled against the
top-k/top-p FILTERED target distribution — the same distribution the
non-speculative sampler draws from — so speculation preserves serving
semantics, though the realized random streams differ from the
non-speculative path (different draw structure).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from inference_gateway_tpu.ops.sampling import GREEDY_EPS, top_k_nucleus

_TINY = 1e-30


def strip_dist(logits: jnp.ndarray, temps: jnp.ndarray, top_ps: jnp.ndarray,
               top_k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Filtered, renormalized sampling distribution on the top-k strip.

    logits (..., V); temps/top_ps broadcastable to logits[..., 0].
    Returns (probs (..., k), idx (..., k)) — probs sum to 1 over the
    nucleus, 0 outside it. At eps-temperature this is one-hot on the
    argmax (ties broken by index order, same as jnp.argmax). Shares the
    exact filter the non-speculative samplers use (ops/sampling.
    top_k_nucleus) — speculation must verify against the SAME
    distribution serving samples from.
    """
    scaled = logits.astype(jnp.float32) / jnp.maximum(temps, GREEDY_EPS)[..., None]
    filtered, idx = top_k_nucleus(scaled, top_ps, top_k)
    # softmax over the -inf-masked strip IS the renormalized nucleus.
    return jax.nn.softmax(filtered, axis=-1), idx


def strip_prob_of(probs: jnp.ndarray, idx: jnp.ndarray, token: jnp.ndarray) -> jnp.ndarray:
    """Probability the strip assigns to ``token`` (0 if absent)."""
    return jnp.where(idx == token[..., None], probs, 0.0).sum(-1)


def strip_sample(probs: jnp.ndarray, idx: jnp.ndarray, gumbel: jnp.ndarray,
                 greedy: jnp.ndarray) -> jnp.ndarray:
    """Sample from a strip distribution via the gumbel trick; greedy rows
    take the strip's argmax (deterministic, index-ordered ties)."""
    logp = jnp.where(probs > 0, jnp.log(jnp.maximum(probs, _TINY)), -jnp.inf)
    j_sample = jnp.argmax(logp + gumbel, axis=-1)
    j_greedy = jnp.argmax(probs, axis=-1)
    j = jnp.where(greedy, j_greedy, j_sample)
    return jnp.take_along_axis(idx, j[..., None], axis=-1)[..., 0]


def residual_dist(p_probs: jnp.ndarray, p_idx: jnp.ndarray,
                  q_probs: jnp.ndarray, q_idx: jnp.ndarray) -> jnp.ndarray:
    """norm(max(p - q, 0)) expressed on p's strip.

    q's mass is aligned onto p's indices by an O(k²) index match (k=64:
    trivial). Residual support is a subset of p's strip by construction.
    Degenerate all-zero residual (p ≡ q) falls back to p itself.
    """
    q_on_p = jnp.where(
        q_idx[..., None, :] == p_idx[..., :, None], q_probs[..., None, :], 0.0
    ).sum(-1)
    r = jnp.maximum(p_probs - q_on_p, 0.0)
    denom = r.sum(-1, keepdims=True)
    return jnp.where(denom > 1e-9, r / jnp.maximum(denom, _TINY), p_probs)


def spec_accept(
    p_probs: jnp.ndarray,  # (S, K+1, k) target strip dists at positions P..P+K
    p_idx: jnp.ndarray,
    q_probs: jnp.ndarray,  # (S, K, k) draft strip dists for proposals 1..K
    q_idx: jnp.ndarray,
    draft_tokens: jnp.ndarray,  # (S, K) the draft's proposals
    uniforms: jnp.ndarray,  # (S, K) acceptance draws
    extra_gumbel: jnp.ndarray,  # (S, k) for the rejected/bonus extra token
    greedy: jnp.ndarray,  # (S,) bool
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized accept/reject. Returns (out_tokens (S, K+1), counts (S,)).

    out_tokens[s, :counts[s]] are the emitted tokens: the accepted draft
    prefix plus one extra — the residual resample at the first rejection,
    or a bonus draw from the target's last distribution if all K drafts
    were accepted. Entries beyond counts are meaningless.
    """
    S, K = draft_tokens.shape
    p_at_d = strip_prob_of(p_probs[:, :K], p_idx[:, :K], draft_tokens)
    q_at_d = strip_prob_of(q_probs, q_idx, draft_tokens)
    ratio = p_at_d / jnp.maximum(q_at_d, _TINY)
    accept = uniforms < jnp.minimum(ratio, 1.0)
    acc_prefix = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    a = acc_prefix.sum(1)  # (S,) accepted drafts, 0..K

    take = lambda arr, i: jnp.take_along_axis(
        arr, i[:, None, None], axis=1)[:, 0]
    pa_probs, pa_idx = take(p_probs, a), take(p_idx, a)  # target dist at position a
    qa_probs = take(q_probs, jnp.minimum(a, K - 1))
    qa_idx = take(q_idx, jnp.minimum(a, K - 1))

    res_probs = residual_dist(pa_probs, pa_idx, qa_probs, qa_idx)
    # a == K (all accepted): bonus draw from p_K itself, not a residual.
    extra_dist = jnp.where((a == K)[:, None], pa_probs, res_probs)
    extra = strip_sample(extra_dist, pa_idx, extra_gumbel, greedy)

    out = jnp.zeros((S, K + 1), jnp.int32)
    out = out.at[:, :K].set(draft_tokens)
    out = out.at[jnp.arange(S), a].set(extra)
    return out, a + 1
