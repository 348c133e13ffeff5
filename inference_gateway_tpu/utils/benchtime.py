"""Shared device-timing helper for the benchmarks.

The rotated-input timer of benchmarks/kernel_lab.py: every timed call
gets a distinct first argument, so nothing below JAX can answer a
repeat of an identical dispatch from a cache, and warm-up blocks on its
own results, so no un-awaited warm-up dispatch drains inside the timed
region.
"""

from __future__ import annotations

import time


def timeit_device(fn, *args, iters: int = 30, n_variants: int = 4):
    """Mean µs/call of ``fn(*args)`` with the first argument rotated
    across ``n_variants`` distinct buffers. Returns (us_per_call,
    result_of_fn_on_the_original_args)."""
    import jax
    import jax.numpy as jnp

    variants = [args] + [
        ((args[0] + jnp.asarray(i, args[0].dtype)),) + args[1:]
        for i in range(1, n_variants)
    ]
    jax.block_until_ready(fn(*args))  # compile
    warm = [fn(*va) for va in variants]
    jax.block_until_ready(warm)
    t = time.perf_counter()
    out = [fn(*variants[i % n_variants]) for i in range(iters)]
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / iters * 1e6, fn(*args)
