"""Kernel tuning lab: measure paged-decode / flash-prefill variants on chip.

The one on-chip microbench so far (2026-07-29, TinyLlama) showed the
production paged kernel at ~2,450 us/call for B=64 x 512-token slots
— ~60x off the ~41 us HBM roofline for the 32 MiB of KV it streams — and
the flash prefill kernel slower than XLA's einsum at 8x512. This lab
exists to close those gaps with measurements, not guesses. Variants:

- DMA pipeline depth: the production kernel double-buffers single pages
  (2 x 32 KiB in flight); variants run NBUF x PP page rings (up to 16
  outstanding DMAs) so scalar-core DMA issue overhead and HBM latency
  overlap compute instead of serializing 1,024 waits.
- Compute dtype: production casts whole K/V pages to f32 before the
  dots; variants feed the MXU native bf16 with f32 accumulation
  (preferred_element_type), matching the XLA einsum path's dtypes.
- Chunked compute: PP pages per (m, l, acc) fold — fewer, larger
  matmuls and 1/PP as many semaphore waits.

Run: python benchmarks/kernel_lab.py [--iters 30]
Prints one JSON object with us/call + max-err vs the gather oracle.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from inference_gateway_tpu.ops.attention import causal_prefill_mask, gqa_attend
from inference_gateway_tpu.ops.flash_attention import flash_prefill_attention
from inference_gateway_tpu.ops.paged_attention import (
    paged_attention_jax,
    paged_attention_tpu,
)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameterized paged-decode kernel: NBUF-deep ring of PP-page chunks,
# bf16 MXU dots, f32 (m, l, acc) accumulator.
# ---------------------------------------------------------------------------
def _lab_paged_kernel(
    page_table_ref,  # (B, max_pages) SMEM
    length_ref,  # (B, 1) SMEM
    q_ref,  # (1, Hq, D) VMEM
    k_pages_hbm,  # (P, page_size, Hkv*D) ANY
    v_pages_hbm,
    out_ref,  # (1, Hq, D)
    k_buf,  # (NBUF, PP, page_size, Hkv*D) VMEM
    v_buf,
    sems,  # DMA sems (NBUF, 2, PP)
    *,
    page_size: int,
    num_kv_heads: int,
    groups: int,
    head_dim: int,
    nbuf: int,
    pp: int,
):
    b = pl.program_id(0)
    length = length_ref[b, 0]
    n_pages = pl.cdiv(length, page_size)
    n_chunks = pl.cdiv(n_pages, pp)
    scale = head_dim ** -0.5
    Hkv, G, D = num_kv_heads, groups, head_dim
    Hq = Hkv * G
    CT = pp * page_size  # tokens per compute chunk

    def chunk_dmas(slot, chunk):
        """DMA start/wait pairs for every in-range page of `chunk`."""
        for j in range(pp):
            page_pos = chunk * pp + j

            @pl.when(page_pos < n_pages)
            def _(j=j, page_pos=page_pos):
                page_idx = page_table_ref[b, page_pos]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page_idx], k_buf.at[slot, j], sems.at[slot, 0, j]
                ).start()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page_idx], v_buf.at[slot, j], sems.at[slot, 1, j]
                ).start()

    def chunk_wait(slot, chunk):
        for j in range(pp):
            page_pos = chunk * pp + j

            @pl.when(page_pos < n_pages)
            def _(j=j, page_pos=page_pos):
                page_idx = page_table_ref[b, page_pos]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page_idx], k_buf.at[slot, j], sems.at[slot, 0, j]
                ).wait()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page_idx], v_buf.at[slot, j], sems.at[slot, 1, j]
                ).wait()

    # Prologue: fill the ring.
    for c in range(nbuf):
        @pl.when(c < n_chunks)
        def _(c=c):
            chunk_dmas(c, c)

    q = q_ref[0]  # (Hq, D) bf16 — native MXU input

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, nbuf)
        chunk_wait(slot, c)

        # Load the chunk into vregs, then reuse its ring slot for the
        # chunk `nbuf` ahead (the loads above order before the DMA
        # writes via ref effects).
        k_chunk = k_buf[slot].reshape(CT, Hkv * D)
        v_chunk = v_buf[slot].reshape(CT, Hkv * D)

        @pl.when(c + nbuf < n_chunks)
        def _():
            chunk_dmas(slot, c + nbuf)

        token_pos = c * CT + jax.lax.broadcasted_iota(jnp.int32, (1, CT), 1)
        valid = token_pos < length

        score_rows = []
        for h in range(Hkv):
            k_h = k_chunk[:, h * D:(h + 1) * D]  # (CT, D) bf16
            q_h = q[h * G:(h + 1) * G]  # (G, D) bf16
            score_rows.append(jax.lax.dot_general(
                q_h, k_h, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))  # (G, CT) f32
        scores = jnp.concatenate(score_rows, axis=0) * scale  # (Hq, CT)
        scores = jnp.where(valid, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_ij = jnp.exp(scores - m_new)
        l_new = l * alpha + jnp.sum(p_ij, axis=-1, keepdims=True)

        p_cast = p_ij.astype(v_chunk.dtype)
        pv_rows = []
        for h in range(Hkv):
            v_h = v_chunk[:, h * D:(h + 1) * D]
            p_h = p_cast[h * G:(h + 1) * G]
            pv_rows.append(jax.lax.dot_general(
                p_h, v_h, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))  # (G, D) f32
        pv = jnp.concatenate(pv_rows, axis=0)

        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((Hq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hq, 1), jnp.float32)
    acc0 = jnp.zeros((Hq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))

    out_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "nbuf", "pp", "interpret"))
def lab_paged_attention(
    q, k_pages, v_pages, page_table, lengths, num_kv_heads: int,
    nbuf: int = 2, pp: int = 4, interpret: bool = False,
):
    B, Hq, D = q.shape
    P, page_size, HkvD = k_pages.shape
    G = Hq // num_kv_heads
    kernel = functools.partial(
        _lab_paged_kernel, page_size=page_size, num_kv_heads=num_kv_heads,
        groups=G, head_dim=D, nbuf=nbuf, pp=pp,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nbuf, pp, page_size, HkvD), k_pages.dtype),
            pltpu.VMEM((nbuf, pp, page_size, HkvD), v_pages.dtype),
            pltpu.SemaphoreType.DMA((nbuf, 2, pp)),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.reshape(B, 1).astype(jnp.int32),
      q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# bench_kernels (ISSUE 12): ragged-vs-gather-vs-bucketed across the
# fallback-layout matrix from ops/paged_attention.paged_dispatch — the
# layouts that USED to force the 10.6×-slower gather path (25,856 µs
# vs 2,448 µs on the chip, 2026-07-29) and now take a kernel. Each layout runs a mixed
# batch (decode rows + one prefill chunk):
#   ragged   — one ragged kernel launch for the whole batch
#   gather   — the pure-JAX ragged reference (the old fallback's cost)
#   bucketed — decode kernel + separate prefill attention (two launches,
#              the pre-ISSUE-12 dispatch shape)
# Run: python benchmarks/kernel_lab.py --suite kernels [--interpret]
# ---------------------------------------------------------------------------
def bench_kernels(iters: int = 30, interpret: bool = False) -> dict:
    from inference_gateway_tpu.ops.paged_attention import (
        paged_dispatch,
        ragged_paged_attention_jax,
        ragged_paged_attention_tpu,
    )

    rng = np.random.default_rng(0)
    # (name, Hq, Hkv, D, tp): the documented fallback matrix. folded =
    # Hkv*D; tp>1 rows report the mesh-dispatch verdict (the kernel
    # itself is measured single-device here — the sharded launch is the
    # same kernel per shard).
    layouts = [
        ("aligned_256", 32, 4, 64, 1),        # classic kernel layout
        ("misaligned_192", 24, 3, 64, 1),     # folded axis off the lane grid
        ("misaligned_head_48", 8, 4, 48, 1),  # odd head_dim, folded 192
        ("gqa_odd_heads_6", 24, 6, 64, 4),    # non-tp-divisible → replicated
        ("tp1_mesh", 32, 4, 64, 0),           # tp=1 multi-device → replicated
    ]
    B, ps, P, mp, seq = (16, 64, 128, 8, 512) if not interpret else (4, 16, 32, 4, 64)
    out: dict = {"platform": jax.devices()[0].platform, "mode":
                 "cpu-interpret (parity evidence)" if interpret else "on-chip"}
    for name, Hq, Hkv, D, tp in layouts:
        # tp=0 is the tp1-multi-device sentinel: tp=1 over an 8-chip mesh.
        path, reason = paged_dispatch(Hkv, Hq, Hkv * D, tp=max(tp, 1),
                                      platform="tpu",
                                      n_devices=8 if tp == 0 else max(tp, 1))
        entry: dict = {"dispatch": path, "reason": reason}
        q_lens = np.array([1] * (B - 1) + [min(seq // 2, mp * ps - 1)], np.int32)
        kv_lens = np.array([min(seq, mp * ps)] * (B - 1) + [int(q_lens[-1])], np.int32)
        q_starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
        T = int(q_lens.sum())
        q = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), jnp.bfloat16)
        pt = jnp.asarray(rng.integers(0, P, (B, mp)), jnp.int32)
        qs, ql, kl = map(jnp.asarray, (q_starts, q_lens, kv_lens))
        try:
            t_g, ref = timeit(lambda *a: ragged_paged_attention_jax(*a, Hkv),
                              q, k, v, pt, qs, ql, kl, iters=iters)
            entry["gather_us"] = round(t_g, 1)
            t_r, got = timeit(
                lambda *a: ragged_paged_attention_tpu(*a, Hkv, interpret=interpret),
                q, k, v, pt, qs, ql, kl, iters=iters)
            entry["ragged_us"] = round(t_r, 1)
            entry["ragged_max_err"] = float(
                jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)).max())
            n_dec = B - 1
            t_d, _ = timeit(
                lambda *a: paged_attention_tpu(*a, Hkv, interpret=interpret),
                q[:n_dec], k, v, pt[:n_dec], kl[:n_dec], iters=iters)
            t_p, _ = timeit(lambda *a: ragged_paged_attention_jax(*a, Hkv),
                            q[n_dec:], k, v, pt[n_dec:],
                            jnp.asarray([0], jnp.int32), ql[n_dec:], kl[n_dec:],
                            iters=iters)
            entry["bucketed_us"] = round(t_d + t_p, 1)
            if entry["ragged_us"]:
                entry["gather_over_ragged"] = round(t_g / t_r, 2)
        except Exception as e:  # keep measuring the other layouts
            entry["error"] = repr(e)[:200]
        out[name] = entry
    return out


from inference_gateway_tpu.utils.benchtime import timeit_device


def timeit(fn, *args, iters=30):
    """us/call with rotated inputs (see utils/benchtime.py for why:
    identical repeated dispatches can be answered from a cache, and
    warm-up must block on its own results)."""
    return timeit_device(fn, *args, iters=iters)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--interpret", action="store_true",
                    help="CPU interpret mode (parity only, tiny shapes)")
    ap.add_argument("--suite", choices=("lab", "kernels"), default="lab",
                    help="'kernels' = ragged-vs-gather-vs-bucketed across the "
                         "paged_dispatch fallback-layout matrix (ISSUE 12)")
    args = ap.parse_args()
    interpret = args.interpret
    if args.suite == "kernels":
        print(json.dumps(bench_kernels(iters=args.iters, interpret=interpret), indent=1))
        return
    out: dict = {"platform": jax.devices()[0].platform}
    rng = np.random.default_rng(0)

    # Serving decode shape: TinyLlama heads, 64 slots, 512 live tokens.
    B, Hq, Hkv, D, ps = 64, 32, 4, 64, 64
    P, mp = 512, 16
    if interpret:
        B, P, mp = 4, 16, 8
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), jnp.bfloat16)
    pt = jnp.asarray(rng.integers(0, P, (B, mp)), jnp.int32)
    lengths = jnp.full((B,), min(mp * ps, 512), jnp.int32)

    t_ref, ref = timeit(
        lambda *a: paged_attention_jax(*a, Hkv), q, k, v, pt, lengths,
        iters=args.iters)
    out["paged_gather_us"] = round(t_ref, 1)

    t_base, got = timeit(
        lambda *a: paged_attention_tpu(*a, Hkv, interpret=interpret),
        q, k, v, pt, lengths, iters=args.iters)
    out["paged_base_us"] = round(t_base, 1)
    out["paged_base_err"] = float(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)).max())

    for nbuf, pp in [(2, 4), (4, 2), (8, 1), (2, 8), (4, 4)]:
        if pp > pt.shape[1]:
            continue
        try:
            t, got = timeit(
                lambda *a: lab_paged_attention(*a, Hkv, nbuf=nbuf, pp=pp,
                                               interpret=interpret),
                q, k, v, pt, lengths, iters=args.iters)
            err = float(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)).max())
            out[f"paged_nbuf{nbuf}_pp{pp}_us"] = round(t, 1)
            out[f"paged_nbuf{nbuf}_pp{pp}_err"] = err
        except Exception as e:  # keep measuring other variants
            out[f"paged_nbuf{nbuf}_pp{pp}_error"] = repr(e)[:200]

    # Flash prefill shape: 8 x 512 fresh prefill.
    B2, T = (8, 512) if not interpret else (2, 128)
    q2 = jnp.asarray(rng.normal(size=(B2, T, Hq, D)), jnp.bfloat16)
    k2 = jnp.asarray(rng.normal(size=(B2, T, Hkv, D)), jnp.bfloat16)
    v2 = jnp.asarray(rng.normal(size=(B2, T, Hkv, D)), jnp.bfloat16)
    l2 = jnp.full((B2,), T, jnp.int32)
    pos2 = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B2, T))
    mask = causal_prefill_mask(pos2, l2)
    t_e, ref2 = timeit(jax.jit(lambda q, k, v: gqa_attend(q, k, v, mask)),
                       q2, k2, v2, iters=args.iters)
    out["prefill_einsum_us"] = round(t_e, 1)
    for bq, bk in [(128, 128), (256, 256), (512, 128), (128, 512), (256, 512), (512, 512)]:
        if bq > T or bk > T:
            continue
        try:
            t, got2 = timeit(
                lambda q, k, v: flash_prefill_attention(
                    q, k, v, l2, block_q=bq, block_k=bk, interpret=interpret),
                q2, k2, v2, iters=args.iters)
            err = float(jnp.abs(got2.astype(jnp.float32) - ref2.astype(jnp.float32)).max())
            out[f"flash_bq{bq}_bk{bk}_us"] = round(t, 1)
            out[f"flash_bq{bq}_bk{bk}_err"] = err
        except Exception as e:
            out[f"flash_bq{bq}_bk{bk}_error"] = repr(e)[:200]

    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
