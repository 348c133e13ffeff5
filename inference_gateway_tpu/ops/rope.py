"""Rotary position embeddings (RoPE), Llama conventions.

Half-split rotate convention (matches HF Llama numerics), with optional
Llama-3.1 frequency scaling. Computed on the fly from positions so decode
steps and ragged prefill share one code path; everything is jit-traceable
with static shapes.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_inv_freq(head_dim: int, theta: float, scaling: dict | None = None) -> jnp.ndarray:
    """Inverse frequencies (head_dim//2,), optionally Llama-3.1-scaled.

    ``scaling`` mirrors HF's ``rope_scaling`` dict for rope_type="llama3":
    factor, low_freq_factor, high_freq_factor, original_max_position_embeddings;
    or for "yarn" (ISSUE 47): factor, original_max_position_embeddings,
    beta_fast, beta_slow.
    """
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling and scaling.get("rope_type", scaling.get("type")) == "yarn":
        # NTK-by-parts: a pair that turns more than beta_fast times over the
        # original context keeps its frequency, one that turns fewer than
        # beta_slow times has it divided by ``factor``, a linear ramp over
        # the pair index between the two correction dims.
        old_len = scaling["original_max_position_embeddings"]

        def correction_dim(turns):
            return head_dim * math.log(old_len / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
        high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))), head_dim - 1)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
        return inv_freq / scaling["factor"] * ramp + inv_freq * (1 - ramp)
    if scaling and scaling.get("rope_type", scaling.get("type")) == "llama3":
        factor = scaling["factor"]
        low = scaling["low_freq_factor"]
        high = scaling["high_freq_factor"]
        old_len = scaling["original_max_position_embeddings"]
        wavelen = 2 * jnp.pi / inv_freq
        # Three bands: keep high-freq, scale low-freq by 1/factor, smooth in between.
        smooth = (old_len / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / factor
        inv_freq = (1 - smooth) * scaled + smooth * inv_freq
    return inv_freq


def yarn_mscale(scaling: dict | None) -> float:
    """YaRN's attention temperature m = 0.1 * mscale_all_dim * ln(factor) + 1
    (1 without YaRN or at a factor of 1 or less): the scores are multiplied
    by m * m. cos and sin carry mscale's ratio to mscale_all_dim, which is 1
    wherever the two are equal: the one case built."""
    if not scaling or scaling.get("rope_type", scaling.get("type")) != "yarn" or scaling["factor"] <= 1:
        return 1.0
    all_dim = scaling.get("mscale_all_dim", 0)
    if scaling.get("mscale", 1) != all_dim:
        raise ValueError("yarn: mscale and mscale_all_dim differ; cos and sin would carry their ratio, which is not built")
    return 0.1 * all_dim * math.log(scaling["factor"]) + 1.0


def rope_cos_sin(positions: jnp.ndarray, inv_freq: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions.

    positions: (..., T) int32 -> cos, sin of shape (..., T, head_dim).
    """
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., T, D/2)
    angles = jnp.concatenate([angles, angles], axis=-1)  # (..., T, D)
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Apply RoPE to (..., T, H, D) given cos/sin of shape (..., T, D)."""
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    out = x.astype(jnp.float32) * cos + _rotate_half(x.astype(jnp.float32)) * sin
    return out.astype(x.dtype)
