"""Ragged paged attention: decode rows and prefill rows in one launch.

The serving hot op (SURVEY.md §7 stage 5; RPA paper in PAPERS.md,
arxiv 2604.15464): attention over a slot's KV pages, reading *only* the
pages a sequence actually occupies so bandwidth is proportional to live
tokens instead of the cache's static max length — the core
paged-attention win.

Layout: kv pages are (num_pages, page_size, Hkv*D) with heads folded
into the last axis. The DMA'd minor dimension is lane-PADDED inside the
kernels' VMEM scratch (Mosaic wants 128 lanes; D alone is often 64), so
folded axes that are NOT 128-aligned still take the kernel path — the
page DMA copies the valid Hkv·D columns into a lane-padded buffer and
per-head views slice inside it. The page table is (B, max_pages) int32.

Two call shapes, each with a kernel and a pure-JAX twin:

- ``paged_attention_{jax,tpu}``: one query token per slot (the classic
  decode step). Grid over slot blocks; double-buffered page DMA
  pipelined across the flattened (slot, page) walk.
- ``ragged_paged_attention_{jax,tpu}`` (ISSUE 12): a MIXED batch — the
  packed query axis carries every row's queries back to back, and
  per-row descriptors (q_start, q_len, kv_len) say which queries belong
  to which slot. Decode rows are q_len=1; prefill-chunk rows are
  q_len=chunk, attending the slot's history causally. One launch per
  engine step regardless of how prefill and decode interleave — the
  kernel-looping dispatch shape (PAPERS.md arxiv 2410.23668).

The pure-JAX twins are the numerics oracle and the ONLY remaining
fallback path (non-TPU platforms); every TPU layout — misaligned folded
axes, tp=1 meshes, non-tp-divisible heads included — now dispatches to
a kernel (see ``paged_dispatch``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from inference_gateway_tpu.utils.platform import device_platform, on_tpu

NEG_INF = -1e30

# Mosaic vector lane width. Page scratch buffers are padded up to it so
# folded head axes that are not 128-aligned (Hkv·D % 128 != 0) still run
# the kernels: the page DMA fills only the valid columns, per-head
# slices never read past them, and the pad lanes are dead weight in
# VMEM only (ISSUE 12 — these layouts used to force the gather path).
LANE = 128


def _pad_lanes(n: int) -> int:
    return -(-n // LANE) * LANE


def _page_dst(buf, slot, folded: int):
    """DMA destination for one page: the whole scratch row when the
    folded axis is lane-aligned, else the valid prefix of the padded
    buffer — the ONE place the padding rule lives (both kernels use it;
    per-head compute slices stay inside the valid columns)."""
    if buf.shape[-1] == folded:
        return buf.at[slot]
    return buf.at[slot, :, pl.dslice(0, folded)]

# IG_TPU_PAGED_KERNEL=1/0 forces the kernel choice; captured once at
# import so the contract is explicit (see paged_attention's docstring).
import os as _os

FORCE_PAGED_KERNEL: str | None = _os.environ.get("IG_TPU_PAGED_KERNEL")


# ---------------------------------------------------------------------------
# Reference implementation (also the CPU path)
# ---------------------------------------------------------------------------
def paged_attention_jax(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pages: jnp.ndarray,  # (P, page_size, Hkv*D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, max_pages) int32
    lengths: jnp.ndarray,  # (B,) int32 — valid tokens (0 = inactive slot)
    num_kv_heads: int,
    window: int | None = None,  # sliding window: attend last `window` tokens only
) -> jnp.ndarray:
    B, Hq, D = q.shape
    _, page_size, HkvD = k_pages.shape
    Hkv = num_kv_heads
    G = Hq // Hkv
    max_pages = page_table.shape[1]
    S = max_pages * page_size

    k = k_pages[page_table].reshape(B, S, Hkv, D)
    v = v_pages[page_table].reshape(B, S, Hkv, D)

    qg = q.reshape(B, Hkv, G, D)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k, preferred_element_type=jnp.float32)
    scores = scores * (D ** -0.5)
    valid = jnp.arange(S)[None, :] < lengths[:, None]  # (B, S)
    if window is not None:
        valid = valid & (jnp.arange(S)[None, :] >= lengths[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _paged_attn_kernel(
    # scalar prefetch
    page_table_ref,  # (B, max_pages) SMEM
    length_ref,  # (B, 1) SMEM
    # inputs
    q_ref,  # (SB, Hq, D) VMEM block: this instance's slots
    k_pages_hbm,  # (P, page_size, Hkv*D) in ANY/HBM
    v_pages_hbm,
    # output
    out_ref,  # (SB, Hq, D) VMEM
    # scratch
    k_buf,  # (2, page_size, pad128(Hkv*D)) VMEM — DMA fills [:Hkv*D]
    v_buf,
    sems,  # DMA semaphores (2, 2)
    *,
    page_size: int,
    num_kv_heads: int,
    groups: int,
    head_dim: int,
    window: int | None = None,
    slots_per_block: int = 1,
):
    """SB slots per grid instance, double-buffered page DMA pipelined
    across the FLATTENED (slot, page) sequence: while slot s's page p is
    in the MXU, the next page — slot s's p+1, or slot s+1's first page —
    is in flight. One-slot-per-instance (round ≤4) paid the per-instance
    fixed cost B times per layer-step and stalled on the first page of
    EVERY slot; at decode occupancy (few live pages per slot) those
    bubbles were most of the 92 µs/layer-step in-scan cost the round-3
    profile flagged vs 25 µs standalone (round-4 verdict next #4).
    Inactive slots (length 0) are treated as one fully-masked page so the
    prefetch chain stays regular."""
    g = pl.program_id(0)
    SB = slots_per_block
    scale = head_dim ** -0.5
    Hkv, G, D = num_kv_heads, groups, head_dim
    Hq = Hkv * G
    num_pages_total = k_pages_hbm.shape[0]
    folded = k_pages_hbm.shape[-1]  # valid columns of the padded scratch

    def slen(s):  # s is block-local
        return length_ref[g * SB + s, 0]

    def p_start_of(s):
        # Sliding window: skip whole pages before the window start —
        # decode bandwidth becomes O(window), not O(length) (Mistral
        # semantics, dense counterpart models/llama.py forward decode).
        if window is None:
            return jnp.int32(0)
        return jnp.maximum(slen(s) - window, 0) // page_size

    def n_pages_of(s):
        return jnp.maximum(pl.cdiv(slen(s), page_size), 1)

    def page_dma(buf_slot, s, page_pos):
        # Clamp: an inactive slot's table row may be stale; its fetched
        # page is fully masked but the DMA must stay in bounds. The copy
        # fills only the valid folded columns of the lane-padded buffer.
        page_idx = jnp.clip(page_table_ref[g * SB + s, page_pos], 0, num_pages_total - 1)
        k_dma = pltpu.make_async_copy(
            k_pages_hbm.at[page_idx], _page_dst(k_buf, buf_slot, folded), sems.at[buf_slot, 0])
        v_dma = pltpu.make_async_copy(
            v_pages_hbm.at[page_idx], _page_dst(v_buf, buf_slot, folded), sems.at[buf_slot, 1])
        return k_dma, v_dma

    # Kick off the block's very first page.
    for dma in page_dma(0, jnp.int32(0), p_start_of(0)):
        dma.start()

    def slot_body(s, parity):
        q = q_ref[pl.dslice(s, 1)][0].astype(jnp.float32)
        length = slen(s)
        p0 = p_start_of(s)
        n_p = n_pages_of(s)
        w_start = jnp.int32(0) if window is None else jnp.maximum(length - window, 0)

        def body(p, carry):
            m, l, acc, par = carry  # (Hq,1), (Hq,1), (Hq,D), buf parity

            # Prefetch the next page of the flattened (slot, page) walk.
            in_slot = p + 1 < n_p
            s_next = jnp.where(in_slot, s, s + 1)
            p_next = jnp.where(in_slot, p + 1,
                               p_start_of(jnp.minimum(s + 1, SB - 1)))

            @pl.when(s_next < SB)
            def _():
                for dma in page_dma(1 - par, s_next, p_next):
                    dma.start()

            for dma in page_dma(par, s, p):
                dma.wait()

            k_page = k_buf[par].astype(jnp.float32)  # (page_size, Hkv*D)
            v_page = v_buf[par].astype(jnp.float32)

            token_pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
            valid = token_pos < length  # (1, page_size)
            if window is not None:
                valid = valid & (token_pos >= w_start)

            # Per-kv-head slices of the folded axis; static unroll over Hkv.
            score_rows = []
            for h in range(Hkv):
                k_h = k_page[:, h * D:(h + 1) * D]  # (page_size, D)
                q_h = q[h * G:(h + 1) * G]  # (G, D)
                score_rows.append(jax.lax.dot_general(
                    q_h, k_h, dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ))  # (G, page_size)
            scores = jnp.concatenate(score_rows, axis=0) * scale  # (Hq, page_size)
            scores = jnp.where(valid, scores, NEG_INF)

            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p_ij = jnp.exp(scores - m_new)  # (Hq, page_size)
            l_new = l * alpha + jnp.sum(p_ij, axis=-1, keepdims=True)

            pv_rows = []
            for h in range(Hkv):
                v_h = v_page[:, h * D:(h + 1) * D]  # (page_size, D)
                p_h = p_ij[h * G:(h + 1) * G]  # (G, page_size)
                pv_rows.append(jnp.dot(p_h, v_h, preferred_element_type=jnp.float32))  # (G, D)
            pv = jnp.concatenate(pv_rows, axis=0)  # (Hq, D)

            return m_new, l_new, acc * alpha + pv, 1 - par

        m0 = jnp.full((Hq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((Hq, 1), jnp.float32)
        acc0 = jnp.zeros((Hq, D), jnp.float32)
        m, l, acc, parity = jax.lax.fori_loop(p0, n_p, body, (m0, l0, acc0, parity))

        out = acc / jnp.maximum(l, 1e-20)
        out_ref[pl.dslice(s, 1)] = out[None].astype(out_ref.dtype)
        return parity

    jax.lax.fori_loop(0, SB, slot_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "interpret", "window"))
def paged_attention_tpu(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pages: jnp.ndarray,  # (P, page_size, Hkv*D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, max_pages)
    lengths: jnp.ndarray,  # (B,)
    num_kv_heads: int,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    P, page_size, HkvD = k_pages.shape
    G = Hq // num_kv_heads
    # Largest SB dividing the batch: fewer grid instances (per-instance
    # fixed cost /SB) and a DMA pipeline that flows across slots.
    SB = next(s for s in (8, 4, 2, 1) if B % s == 0)

    kernel = functools.partial(
        _paged_attn_kernel,
        page_size=page_size,
        num_kv_heads=num_kv_heads,
        groups=G,
        head_dim=D,
        window=window,
        slots_per_block=SB,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // SB,),
        in_specs=[
            pl.BlockSpec((SB, Hq, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((SB, Hq, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, _pad_lanes(HkvD)), k_pages.dtype),
            pltpu.VMEM((2, page_size, _pad_lanes(HkvD)), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.reshape(B, 1).astype(jnp.int32), q, k_pages, v_pages)


def paged_attention_sharded(q, k_pages, v_pages, page_table, lengths, num_kv_heads: int,
                            mesh, window: int | None = None,
                            interpret: bool | None = None,
                            replicated: bool = False) -> jnp.ndarray:
    """Pallas kernel under a mesh via shard_map (round-1 verdict next
    #5). Two modes:

    - tp-sharded (default): attention is kv-head-local — each tp shard
      holds Hq/tp query heads and the matching Hkv/tp slice of the
      folded page axis, so the kernel runs per-shard with NO
      collectives — identical comms profile to the GSPMD gather path,
      but with the kernel's O(live tokens) DMA.
    - ``replicated``: every device runs the FULL kernel on the
      replicated arrays (tp=1 meshes, or heads that don't tile tp).
      Duplicate work, zero collectives — and still ~10× cheaper than
      the gather fallback these layouts used to take (ISSUE 12).

    Page table and lengths are replicated host metadata either way."""
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = not on_tpu()
    hkv_local = num_kv_heads if replicated else num_kv_heads // mesh.shape["tp"]

    def local(q_l, k_l, v_l, pt_l, len_l):
        return paged_attention_tpu(q_l, k_l, v_l, pt_l, len_l, hkv_local,
                                   interpret=interpret, window=window)

    rep = P()
    if replicated:
        in_specs = (rep, rep, rep, rep, rep)
        out_spec = rep
    else:
        in_specs = (P(None, "tp", None), P(None, None, "tp"), P(None, None, "tp"),
                    P(None, None), P(None))
        out_spec = P(None, "tp", None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_spec,
        check_vma=False,
    )(q, k_pages, v_pages, page_table, lengths)


# ---------------------------------------------------------------------------
# Ragged paged attention: mixed prefill+decode batches (ISSUE 12)
# ---------------------------------------------------------------------------
def ragged_paged_attention_jax(
    q: jnp.ndarray,  # (T, Hq, D) packed queries, rows back to back
    k_pages: jnp.ndarray,  # (P, page_size, Hkv*D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (R, max_pages) int32, row-aligned
    q_starts: jnp.ndarray,  # (R,) int32 — row r's first packed query index
    q_lens: jnp.ndarray,  # (R,) int32 — row r's query count (0 = inactive)
    kv_lens: jnp.ndarray,  # (R,) int32 — row r's total kv length AFTER this step
    num_kv_heads: int,
    window: int | None = None,
) -> jnp.ndarray:
    """Pure-JAX ragged reference (gather pages → dense masked attention).

    The correctness twin of the ragged kernel and the only remaining
    fallback path (non-TPU platforms). Query j of row r sits at absolute
    position ``kv_lens[r] - q_lens[r] + j`` and attends keys at
    positions ≤ its own — decode rows (q_len=1) reduce exactly to the
    classic paged decode mask, prefill rows to causal chunked prefill.
    Packed positions not covered by any row return zeros."""
    T, Hq, D = q.shape
    R, max_pages = page_table.shape
    _, page_size, _ = k_pages.shape
    Hkv = num_kv_heads
    G = Hq // Hkv
    S = max_pages * page_size

    k = k_pages[page_table].reshape(R, S, Hkv, D)
    v = v_pages[page_table].reshape(R, S, Hkv, D)

    t = jnp.arange(T)
    cover = (t[None, :] >= q_starts[:, None]) & (
        t[None, :] < (q_starts + q_lens)[:, None])  # (R, T)
    valid_t = cover.any(axis=0)
    row_of = jnp.argmax(cover, axis=0)  # (T,) — 0 for uncovered (masked below)
    qpos = kv_lens[row_of] - q_lens[row_of] + (t - q_starts[row_of])

    kt = k[row_of]  # (T, S, Hkv, D)
    vt = v[row_of]
    qg = q.reshape(T, Hkv, G, D)
    scores = jnp.einsum("tkgd,tskd->tkgs", qg, kt,
                        preferred_element_type=jnp.float32) * (D ** -0.5)
    span = jnp.arange(S)
    valid = (span[None, :] <= qpos[:, None]) & (span[None, :] < kv_lens[row_of][:, None])
    if window is not None:
        valid = valid & (span[None, :] > qpos[:, None] - window)
    valid = valid & valid_t[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("tkgs,tskd->tkgd", probs.astype(vt.dtype), vt,
                     preferred_element_type=jnp.float32)
    out = out.reshape(T, Hq, D).astype(q.dtype)
    return jnp.where(valid_t[:, None, None], out, 0)


def _ragged_paged_attn_kernel(
    # scalar prefetch
    page_table_ref,  # (R, max_pages) SMEM
    descr_ref,  # (R, 3) SMEM: q_start, q_len, kv_len per row
    # inputs
    q_ref,  # (T + QB, Hq, D) VMEM — whole packed batch (+QB tile slack)
    k_pages_hbm,  # (P, page_size, Hkv*D) ANY/HBM
    v_pages_hbm,
    # output
    out_ref,  # (T + QB, Hq, D) VMEM
    # scratch
    k_buf,  # (2, page_size, pad128(Hkv*D)) VMEM
    v_buf,
    sems,  # DMA semaphores (2, 2)
    *,
    page_size: int,
    num_kv_heads: int,
    groups: int,
    head_dim: int,
    window: int | None,
    q_block: int,
):
    """Grid over rows; each instance flash-attends its row's packed query
    span against the row's pages in ``q_block``-sized query tiles.

    Tiling scheme (the reason every fallback layout now runs a kernel):
    - The packed query axis is NOT blocked by the grid — the whole batch
      (plus one tile of slack so a tile never clamps at the buffer edge)
      sits in VMEM and rows address their spans with dynamic slices from
      the prefetched descriptors. Mixed-step batches are budget-bounded
      (hundreds of tokens), so this is a few MiB, not a cache.
    - Page scratch is lane-padded: a misaligned folded axis (Hkv·D not a
      multiple of 128) DMAs into the valid prefix of a 128-aligned
      buffer; per-head compute slices stay inside the valid columns.
    - Query tiles beyond a row's q_len are masked, and their output
      lanes preserve-and-defer: each row read-modify-writes its tile
      window, grid iterations are sequential, and every valid packed
      position is written exactly once by its owning row.

    Decode rows (q_len=1) walk their pages like the classic decode
    kernel; prefill rows reuse the same double-buffered DMA walk with a
    per-query causal mask — one launch for the whole mixed batch.
    """
    r = pl.program_id(0)
    QB = q_block
    scale = head_dim ** -0.5
    Hkv, G, D = num_kv_heads, groups, head_dim
    folded = k_pages_hbm.shape[-1]
    num_pages_total = k_pages_hbm.shape[0]

    # First grid step zeroes the output block: uncovered packed lanes
    # must read as zeros, not leftover VMEM.
    @pl.when(r == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    q_start = descr_ref[r, 0]
    q_len = descr_ref[r, 1]
    kv_len = descr_ref[r, 2]

    def page_dma(buf_slot, page_pos):
        page_idx = jnp.clip(page_table_ref[r, page_pos], 0, num_pages_total - 1)
        return (pltpu.make_async_copy(k_pages_hbm.at[page_idx],
                                      _page_dst(k_buf, buf_slot, folded),
                                      sems.at[buf_slot, 0]),
                pltpu.make_async_copy(v_pages_hbm.at[page_idx],
                                      _page_dst(v_buf, buf_slot, folded),
                                      sems.at[buf_slot, 1]))

    @pl.when(q_len > 0)
    def _row():
        kv_start = kv_len - q_len  # absolute position of the row's first query
        if window is None:
            p0 = jnp.int32(0)
        else:
            # Earliest page any of the row's queries can see: the first
            # query's window start (later queries see later keys only).
            p0 = jnp.maximum(kv_start + 1 - window, 0) // page_size
        n_tiles = pl.cdiv(q_len, QB)

        def tile_body(c, _):
            tile0 = q_start + c * QB
            q_tile = q_ref[pl.dslice(tile0, QB)].astype(jnp.float32)  # (QB, Hq, D)
            # Per-query-row absolute positions, expanded per group so the
            # (QB·G, page_size) score mask indexes naturally.
            qrow = c * QB + jax.lax.broadcasted_iota(jnp.int32, (QB * G, 1), 0) // G
            qpos = kv_start + qrow  # (QB*G, 1)
            in_row = qrow < q_len
            # The tile's causal horizon bounds its page walk: queries in
            # tile c see keys < kv_start + (c+1)·QB, so later pages are
            # fully masked and need not be DMA'd — the walk covers the
            # causal triangle, not the full rectangle (review finding).
            tile_kv = jnp.minimum(kv_start + (c + 1) * QB, kv_len)
            n_pages_t = jnp.maximum(pl.cdiv(tile_kv, page_size), 1)

            def page_body(p, carry):
                par = carry[0]
                accs = carry[1:]

                @pl.when(p + 1 < n_pages_t)
                def _():
                    for dma in page_dma(1 - par, p + 1):
                        dma.start()

                for dma in page_dma(par, p):
                    dma.wait()
                k_page = k_buf[par].astype(jnp.float32)  # (ps, pad)
                v_page = v_buf[par].astype(jnp.float32)

                token_pos = p * page_size + jax.lax.broadcasted_iota(
                    jnp.int32, (1, page_size), 1)  # (1, ps)
                valid = (token_pos <= qpos) & in_row
                if window is not None:
                    valid = valid & (token_pos > qpos - window)

                new_accs = []
                for h in range(Hkv):
                    m, l, acc = accs[3 * h], accs[3 * h + 1], accs[3 * h + 2]
                    q_h = q_tile[:, h * G:(h + 1) * G, :].reshape(QB * G, D)
                    k_h = k_page[:, h * D:(h + 1) * D]  # (ps, D)
                    s_h = jax.lax.dot_general(
                        q_h, k_h, dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale  # (QB*G, ps)
                    s_h = jnp.where(valid, s_h, NEG_INF)
                    m_new = jnp.maximum(m, jnp.max(s_h, axis=-1, keepdims=True))
                    alpha = jnp.exp(m - m_new)
                    p_h = jnp.exp(s_h - m_new)
                    l_new = l * alpha + jnp.sum(p_h, axis=-1, keepdims=True)
                    v_h = v_page[:, h * D:(h + 1) * D]  # (ps, D)
                    pv = jnp.dot(p_h, v_h, preferred_element_type=jnp.float32)
                    new_accs.extend((m_new, l_new, acc * alpha + pv))
                return (1 - par,) + tuple(new_accs)

            init = (jnp.int32(0),)
            for _h in range(Hkv):
                init += (jnp.full((QB * G, 1), NEG_INF, jnp.float32),
                         jnp.zeros((QB * G, 1), jnp.float32),
                         jnp.zeros((QB * G, D), jnp.float32))
            for dma in page_dma(0, p0):
                dma.start()
            final = jax.lax.fori_loop(p0, n_pages_t, page_body, init)

            valid_q = (c * QB + jax.lax.broadcasted_iota(
                jnp.int32, (QB, 1, 1), 0)) < q_len  # (QB, 1, 1)
            for h in range(Hkv):
                _m, l, acc = final[1 + 3 * h], final[2 + 3 * h], final[3 + 3 * h]
                out_h = (acc / jnp.maximum(l, 1e-20)).reshape(QB, G, D)
                prev = out_ref[pl.dslice(tile0, QB), h * G:(h + 1) * G, :]
                out_ref[pl.dslice(tile0, QB), h * G:(h + 1) * G, :] = jnp.where(
                    valid_q, out_h, prev.astype(jnp.float32)).astype(out_ref.dtype)
            return 0

        jax.lax.fori_loop(0, n_tiles, tile_body, 0)


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "interpret", "window",
                                             "q_block"))
def ragged_paged_attention_tpu(
    q: jnp.ndarray,  # (T, Hq, D) packed queries
    k_pages: jnp.ndarray,  # (P, page_size, Hkv*D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (R, max_pages)
    q_starts: jnp.ndarray,  # (R,)
    q_lens: jnp.ndarray,  # (R,)
    kv_lens: jnp.ndarray,  # (R,)
    num_kv_heads: int,
    interpret: bool = False,
    window: int | None = None,
    q_block: int = 8,
) -> jnp.ndarray:
    T, Hq, D = q.shape
    P, page_size, HkvD = k_pages.shape
    R = page_table.shape[0]
    G = Hq // num_kv_heads
    QB = max(1, min(q_block, T))
    # One tile of slack so a row's last tile never clamps at the buffer
    # edge (a clamped dynamic slice would shift the tile window off the
    # mask's indexing). Sliced back off below.
    qp = jnp.pad(q, ((0, QB), (0, 0), (0, 0)))

    kernel = functools.partial(
        _ragged_paged_attn_kernel,
        page_size=page_size,
        num_kv_heads=num_kv_heads,
        groups=G,
        head_dim=D,
        window=window,
        q_block=QB,
    )
    descr = jnp.stack([q_starts, q_lens, kv_lens], axis=1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((T + QB, Hq, D), lambda r, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((T + QB, Hq, D), lambda r, *_: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, _pad_lanes(HkvD)), k_pages.dtype),
            pltpu.VMEM((2, page_size, _pad_lanes(HkvD)), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + QB, Hq, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), descr, qp, k_pages, v_pages)
    return out[:T]


def ragged_paged_attention_sharded(q, k_pages, v_pages, page_table, q_starts, q_lens,
                                   kv_lens, num_kv_heads: int, mesh,
                                   window: int | None = None,
                                   interpret: bool | None = None,
                                   replicated: bool = False,
                                   q_block: int = 8) -> jnp.ndarray:
    """Ragged kernel under a mesh: kv-head-local per tp shard (no
    collectives, same layout algebra as paged_attention_sharded), or
    fully replicated for tp=1 meshes / non-tp-divisible heads.
    Descriptors and the page table are replicated host metadata."""
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = not on_tpu()
    hkv_local = num_kv_heads if replicated else num_kv_heads // mesh.shape["tp"]

    def local(q_l, k_l, v_l, pt_l, qs_l, ql_l, kl_l):
        return ragged_paged_attention_tpu(q_l, k_l, v_l, pt_l, qs_l, ql_l, kl_l,
                                          hkv_local, interpret=interpret,
                                          window=window, q_block=q_block)

    rep = P()
    if replicated:
        in_specs = (rep,) * 7
        out_spec = rep
    else:
        in_specs = (P(None, "tp", None), P(None, None, "tp"), P(None, None, "tp"),
                    rep, rep, rep, rep)
        out_spec = P(None, "tp", None)
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_spec, check_vma=False,
    )(q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens)


# The one on-chip reading of this pair (2026-07-29, TinyLlama serving
# shape, before the ragged kernel existed): gather 25,856 µs vs kernel
# 2,448 µs per call — the GSPMD gather fallback ~10.6× SLOWER than the
# Pallas kernel. A serving layout must not land on it by accident;
# paged_dispatch below is the single decision point and
# tests/test_paged_dispatch.py pins every committed profile
# (serving/profiles.py) to a kernel path.
GATHER_FALLBACK_SLOWDOWN = 10.6


def paged_dispatch(num_kv_heads: int, num_q_heads: int, folded_dim: int,
                   tp: int = 1, platform: str = "tpu", n_devices: int = 1,
                   force: str | None = None) -> tuple[str, str]:
    """The ONE decision for which paged-attention path a layout takes.

    Returns (path, reason); path ∈ {"kernel", "kernel_sharded",
    "kernel_replicated", "gather"}. ``folded_dim`` is the pages' minor
    axis Hkv·D. Pure function of the layout so profiles/tests can audit
    dispatch without building arrays (round-4 verdict next #10: the
    10.6×-slower gather fallback must be an assertion, not an accident).

    ISSUE 12 closed the fallback matrix: lane-padded page scratch
    handles non-128-aligned folded axes inside the kernels, and a
    replicated shard_map launch covers tp=1 multi-device meshes and
    non-tp-divisible heads (duplicate per-device work, zero collectives
    — still ~10× cheaper than the gather these layouts used to take).
    The ONLY remaining gather layouts:
    - any non-TPU platform (CPU/GPU test runs) — the pure-JAX ragged
      reference is the correctness twin there;
    - IG_TPU_PAGED_KERNEL=0 (the explicit kill switch).
    """
    if force == "0":
        return "gather", "forced off by IG_TPU_PAGED_KERNEL=0"
    if force != "1" and platform != "tpu":
        return "gather", f"platform {platform} is not TPU (pure-JAX ragged reference)"
    forced = " (forced by IG_TPU_PAGED_KERNEL=1)" if force == "1" else ""
    if tp > 1:
        if num_kv_heads % tp or num_q_heads % tp:
            return "kernel_replicated", (
                f"heads not tp-divisible (Hkv={num_kv_heads}, Hq={num_q_heads}, "
                f"tp={tp}): replicated shard_map launch, no collectives{forced}")
        return "kernel_sharded", (
            f"shard_map over tp={tp}, kv-head-local, no collectives{forced}")
    if n_devices != 1:
        return "kernel_replicated", (
            f"{n_devices}-device mesh with tp=1: replicated shard_map launch, "
            f"no collectives{forced}")
    if folded_dim % LANE:
        return "kernel", (
            f"single-device TPU; folded axis {folded_dim} rides the lane-padded "
            f"scratch{forced}")
    return "kernel", f"single-device TPU, lane-aligned{forced}"


def _mesh_devices(mesh) -> int:
    """Device count the dispatch decision sees: a mesh's size when one
    is in play, else 1 — with no mesh the arrays live on one device and
    a plain kernel launch is correct regardless of what is visible."""
    return int(mesh.devices.size) if mesh is not None else 1


def paged_attention(q, k_pages, v_pages, page_table, lengths, num_kv_heads: int,
                    use_kernel: bool | None = None, window: int | None = None,
                    mesh=None) -> jnp.ndarray:
    """Dispatch (see paged_dispatch): Pallas kernel on single-device
    TPU, shard_mapped over ``tp`` under a mesh (kv-head-local), or a
    replicated shard_map launch for tp=1 meshes / non-tp-divisible
    heads; the XLA gather path only off-TPU (~10.6× slower at serving
    shape). ``IG_TPU_PAGED_KERNEL=1/0`` forces the kernel choice (tests
    exercise the shard_map path on a CPU mesh in interpret mode). The
    flag is captured at import (module attr FORCE_PAGED_KERNEL) —
    jitted forwards bake the dispatch into the trace, so a mid-session
    env flip would not apply to compiled shapes (advisor round-2)."""
    force = FORCE_PAGED_KERNEL
    platform = device_platform()
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if use_kernel is not None and force is None and tp == 1:
        # Explicit caller override (tests); force flag still wins above.
        path = "kernel" if use_kernel else "gather"
    else:
        path, _ = paged_dispatch(
            num_kv_heads, q.shape[1], k_pages.shape[-1], tp=tp,
            platform=platform, n_devices=_mesh_devices(mesh), force=force)
    interpret = platform != "tpu"
    if path in ("kernel_sharded", "kernel_replicated") and mesh is not None:
        return paged_attention_sharded(q, k_pages, v_pages, page_table, lengths,
                                       num_kv_heads, mesh, window=window,
                                       replicated=path == "kernel_replicated")
    if path in ("kernel", "kernel_sharded", "kernel_replicated"):
        return paged_attention_tpu(q, k_pages, v_pages, page_table, lengths, num_kv_heads,
                                   window=window, interpret=interpret)
    return paged_attention_jax(q, k_pages, v_pages, page_table, lengths, num_kv_heads,
                               window=window)


def ragged_paged_attention(q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens,
                           num_kv_heads: int, window: int | None = None,
                           mesh=None, q_block: int = 8) -> jnp.ndarray:
    """Dispatch for the mixed-batch ragged op (ISSUE 12): same decision
    table as ``paged_attention`` (paged_dispatch is the single source),
    applied to the ragged kernel/reference pair. The pure-JAX ragged
    reference is the correctness twin and the only non-TPU path."""
    force = FORCE_PAGED_KERNEL
    platform = device_platform()
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    path, _ = paged_dispatch(
        num_kv_heads, q.shape[1], k_pages.shape[-1], tp=tp,
        platform=platform, n_devices=_mesh_devices(mesh), force=force)
    interpret = platform != "tpu"
    if path in ("kernel_sharded", "kernel_replicated") and mesh is not None:
        return ragged_paged_attention_sharded(
            q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens,
            num_kv_heads, mesh, window=window,
            replicated=path == "kernel_replicated", q_block=q_block)
    if path in ("kernel", "kernel_sharded", "kernel_replicated"):
        return ragged_paged_attention_tpu(
            q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens,
            num_kv_heads, interpret=interpret, window=window, q_block=q_block)
    return ragged_paged_attention_jax(
        q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens,
        num_kv_heads, window=window)
