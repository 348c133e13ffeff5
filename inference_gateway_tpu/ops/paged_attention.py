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
  decode step). One instance a call; double-buffered page DMA pipelined
  across the flattened (slot, visit) walk, several pages a visit.
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

import collections
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
    # An empty slot's row is zeros, as the kernel writes it.
    return jnp.where((lengths > 0)[:, None, None], out.reshape(B, Hq, D), 0).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
# The window operand of a global layer (``window_dyn``): past any context.
GLOBAL_WINDOW = 1 << 30

# The decode kernel walks a slot's pages several at a time (ISSUE 37): a
# VISIT has up to ``pages_per_visit`` consecutive pages in flight and scores,
# soft-maxes and weighs them as one (Hq, N·page_size) tile. Both page buffers
# (K and V, each double-buffered) and the visit's float32 scores and
# probabilities have to fit this much VMEM; the layout decides N.
DECODE_VISIT_BYTES = 4 << 20


def decode_walk_plan(num_q_heads: int, num_kv_heads: int, head_dim: int, page_size: int,
                     max_pages: int, dtype=jnp.bfloat16) -> dict:
    """The decode kernel's page walk for a layout: static per engine, so it
    is a /debug/status entry (``decode_attention``)."""
    itemsize = jnp.dtype(dtype).itemsize
    page = page_size * _pad_lanes(num_kv_heads * head_dim) * itemsize
    rows = num_kv_heads * 8 * -(-num_q_heads // num_kv_heads // 8)  # G on the sublane grid
    tile = rows * page_size * (4 + itemsize)  # a page's scores and probabilities
    n = max(1, min(DECODE_VISIT_BYTES // (2 * 2 * page + tile), max_pages))
    return {"pages_per_visit": n, "operand_dtype": jnp.dtype(dtype).name, "skips_empty_slots": True}


def _div(a, b: int):
    """a // b of scalars that are never negative (one equation where
    ``//`` traces eleven to round towards minus infinity)."""
    return jax.lax.div(a, jnp.int32(b))


def _each_head(num_kv_heads: int, head_dim: int, body) -> None:
    """``body(h)`` for every KV head: a loop traced once where a head's
    lane slice sits on the 128 grid, else static slices. Unrolled when
    it is lowered, so that the chip's scheduler overlaps one head's
    product with the next one's: left as a loop the ragged kernel takes
    1.4-2.5 times as long (PERF.md §6, PR 34)."""
    if head_dim % LANE:
        for h in range(num_kv_heads):
            body(h)
    else:
        jax.lax.fori_loop(0, num_kv_heads, lambda h, c: (body(h), c)[1], 0, unroll=True)


def _paged_attn_kernel(
    # scalar prefetch
    page_table_ref,  # (B + 1, max_pages) SMEM
    length_ref,  # (B + 1,) SMEM: the slots' lengths, -1; then the window where it is an operand
    # inputs
    q_ref,  # (B, Hkv, G, D) VMEM: every slot's query, by KV head
    k_pages_hbm,  # (P, page_size, Hkv*D) in ANY/HBM
    v_pages_hbm,
    # output
    out_ref,  # (B, Hkv, G, D) VMEM
    # scratch
    k_buf,  # (2, N, page_size, pad128(Hkv*D)) VMEM — a page's DMA fills [:Hkv*D]
    v_buf,
    sems,  # DMA semaphores (2, 2)
    s_ref,  # (Hkv, G, N·page_size) float32: a visit's scores
    p_ref,  # the same in V's dtype: its probabilities
    m_ref,  # (Hkv, G, 1) float32: the slot's running maximum,
    l_ref,  # sum
    acc_ref,  # (Hkv, G, D) and output
    *,
    window: int | None,
    window_operand: bool,
):
    """One instance for the whole batch. The walk is one chain of VISITS
    over the slots that hold something: a visit is up to N consecutive
    pages of a slot, DMA'd together into one half of the page buffers
    while the other half is in the MXU; a slot's last visit starts the
    first of the next slot WITH a length, so an empty slot costs neither a
    DMA nor a flash update and its output row is zeros.

    A visit's pages are scored, soft-maxed and weighed as ONE (G,
    N·page_size) tile a KV head: one loop over heads for the scores, one
    flash update over every head's tile, one loop over heads for the
    second product, the loops traced once. Operands reach the MXU in the
    dtype they are stored in, the probabilities in V's, sums in float32:
    the arithmetic of ``paged_attention_jax``. A window's first visit and
    a context's last are ragged: only their live pages are fetched, what
    the rest of the buffer holds is masked (V's buffer starts as zeros, so
    a masked column never weighs a NaN).

    The window is static (``window``: a model whose layers are alike pays
    no mask without one) or the last of ``length_ref``
    (``window_operand``: a model that mixes windowed and global layers
    traces ONE instance, a global layer handing GLOBAL_WINDOW)."""
    B, Hkv, G, D = q_ref.shape
    _, N, page_size, _ = k_buf.shape
    scale = D ** -0.5
    last_page = k_pages_hbm.shape[0] - 1
    folded = k_pages_hbm.shape[-1]  # valid columns of the padded scratch
    zero = jnp.int32(0)
    if window_operand:
        window = length_ref[B + 1]

    def live_from(i):
        """The first slot at or after ``i`` that holds something; B if none
        (``length_ref[B]`` is -1: a slot of no pages that ends the search)."""
        return jax.lax.while_loop(lambda i: length_ref[i] == 0, lambda i: i + 1, i)

    def span(s):
        """(first page, pages) of slot ``s``'s walk. Sliding window: whole
        pages before the window start are skipped — decode bandwidth is
        O(window), not O(length)."""
        length = length_ref[s]
        pages = _div(length + (page_size - 1), page_size)
        if window is None:
            return zero, pages
        first = _div(jnp.maximum(length - window, 0), page_size)
        return first, pages - first

    def page_dma(par, idx, i):
        # The copy fills only the valid folded columns of the padded buffer.
        return (pltpu.make_async_copy(k_pages_hbm.at[idx], _page_dst(k_buf.at[par], i, folded),
                                      sems.at[par, 0]),
                pltpu.make_async_copy(v_pages_hbm.at[idx], _page_dst(v_buf.at[par], i, folded),
                                      sems.at[par, 1]))

    def fetch(par, s, page, n):
        """Start the DMAs of ``n`` pages of slot ``s`` from ``page`` on."""
        def one(i, c):
            # Clamped: a stale table entry must not take the DMA out of bounds.
            idx = jnp.minimum(jnp.maximum(page_table_ref[s, page + i], 0), last_page)
            for dma in page_dma(par, idx, i):
                dma.start()
            return c

        jax.lax.fori_loop(zero, n, one, 0)

    def arrived(par, n):
        def one(i, c):
            for dma in page_dma(par, 0, i):  # a wait counts bytes: any page's
                dma.wait()
            return c

        jax.lax.fori_loop(zero, n, one, 0)

    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
    first = live_from(zero)
    first_page, first_pages = span(first)
    fetch(0, first, first_page, jnp.minimum(first_pages, N))

    def slot_body(carry):
        s, p0, pages, par = carry
        length = length_ref[s]
        # Whose first visit this slot's last one starts.
        nxt = live_from(s + 1)
        nxt_p0, nxt_pages = span(nxt)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def visit(done, par):
            """The slot's pages from its ``done``-th on, N at the most."""
            left = pages - done - N
            more = left > 0
            fetch(1 - par, jax.lax.select(more, s, nxt), jax.lax.select(more, p0 + done + N, nxt_p0),
                  jnp.minimum(jax.lax.select(more, left, nxt_pages), N))
            arrived(par, jnp.minimum(left + N, N))

            def score(h):
                k_h = k_buf[par, :, :, pl.ds(h * D, D)].reshape(N * page_size, D)
                s_ref[h] = jax.lax.dot_general(
                    q_ref[s, h], k_h.astype(q_ref.dtype), dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # (G, N·page_size)

            _each_head(Hkv, D, score)
            tile = s_ref.shape
            pos = (p0 + done) * page_size + jax.lax.broadcasted_iota(jnp.int32, tile, 2)
            valid = pos < length
            if window is not None:
                valid = valid & (pos >= length - window)
            # Every KV head's scores under ONE flash update a visit.
            sc = jax.lax.select(valid, s_ref[...] * scale, jnp.full(tile, NEG_INF, jnp.float32))
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            probs = jnp.exp(sc - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
            m_ref[...] = m_new
            acc_ref[...] = acc_ref[...] * alpha
            p_ref[...] = probs.astype(p_ref.dtype)

            def weigh(h):
                v_h = v_buf[par, :, :, pl.ds(h * D, D)].reshape(N * page_size, D)
                acc_ref[h] += jnp.dot(p_ref[h], v_h, preferred_element_type=jnp.float32)

            _each_head(Hkv, D, weigh)
            return done + N, 1 - par

        _, par = jax.lax.while_loop(lambda c: c[0] < pages, lambda c: visit(*c), (zero, par))
        out_ref[s] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)
        return nxt, nxt_p0, nxt_pages, par

    jax.lax.while_loop(lambda c: c[0] < B, slot_body, (first, first_page, first_pages, zero))


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "interpret", "window", "pages_per_visit"))
def paged_attention_tpu(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pages: jnp.ndarray,  # (P, page_size, Hkv*D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, max_pages)
    lengths: jnp.ndarray,  # (B,)
    num_kv_heads: int,
    interpret: bool = False,
    window: int | None = None,
    window_dyn: jnp.ndarray | None = None,  # int32 scalar: the window as an operand
    pages_per_visit: int | None = None,  # the layout's (decode_walk_plan) unless a test says
) -> jnp.ndarray:
    B, Hq, D = q.shape
    P, page_size, HkvD = k_pages.shape
    G = Hq // num_kv_heads
    assert window is None or window_dyn is None, "one window: static, or the operand"
    N = pages_per_visit or decode_walk_plan(Hq, num_kv_heads, D, page_size, page_table.shape[1],
                                            k_pages.dtype)["pages_per_visit"]
    # The products' operand dtype: what q and the pages are stored in.
    mxu_dtype = jnp.promote_types(q.dtype, k_pages.dtype)

    kernel = functools.partial(_paged_attn_kernel, window=window,
                               window_operand=window_dyn is not None)
    # A slot more, of length -1: it ends the search for the next live slot and
    # holds no page. After it the window, where it is an operand.
    descr = jax.lax.pad(lengths.astype(jnp.int32), jnp.int32(-1), ((0, 1, 0),))
    if window_dyn is not None:
        descr = jnp.concatenate([descr, jnp.reshape(window_dyn, (1,)).astype(jnp.int32)])
    by_head = (B, num_kv_heads, G, D)
    tile = (num_kv_heads, G, N * page_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(by_head, lambda i, *_: (0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec(by_head, lambda i, *_: (0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, N, page_size, _pad_lanes(HkvD)), k_pages.dtype),
            pltpu.VMEM((2, N, page_size, _pad_lanes(HkvD)), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM(tile, jnp.float32),
            pltpu.VMEM(tile, v_pages.dtype),
            pltpu.VMEM((num_kv_heads, G, 1), jnp.float32),
            pltpu.VMEM((num_kv_heads, G, 1), jnp.float32),
            pltpu.VMEM((num_kv_heads, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(by_head, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(jax.lax.pad(page_table.astype(jnp.int32), jnp.int32(0), ((0, 1, 0), (0, 0, 0))),
      descr, q.reshape(by_head).astype(mxu_dtype), k_pages, v_pages)
    return out.reshape(B, Hq, D)


def paged_attention_sharded(q, k_pages, v_pages, page_table, lengths, num_kv_heads: int,
                            mesh, window: int | None = None,
                            interpret: bool | None = None,
                            replicated: bool = False, window_dyn=None) -> jnp.ndarray:
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

    Page table, lengths and the window operand are replicated host
    metadata either way."""
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = not on_tpu()
    hkv_local = num_kv_heads if replicated else num_kv_heads // mesh.shape["tp"]

    def local(q_l, k_l, v_l, pt_l, len_l, *w_l):
        return paged_attention_tpu(q_l, k_l, v_l, pt_l, len_l, hkv_local, interpret=interpret,
                                   window=window, window_dyn=w_l[0] if w_l else None)

    rep = P()
    dyn = () if window_dyn is None else (jnp.asarray(window_dyn, jnp.int32),)
    if replicated:
        in_specs = (rep,) * (5 + len(dyn))
        out_spec = rep
    else:
        in_specs = (P(None, "tp", None), P(None, None, "tp"), P(None, None, "tp"),
                    rep, rep) + (rep,) * len(dyn)
        out_spec = P(None, "tp", None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_spec,
        check_vma=False,
    )(q, k_pages, v_pages, page_table, lengths, *dyn)


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


# The ragged kernel's query tiles (ISSUE 34). A row's queries are walked
# against its pages in tiles, and a tile reads each page it can see once —
# so the width of the tile is how often a row's context is read and how
# many rows one MXU pass carries. A row of ONE query (a decode row) is a
# tile of its own: G rows a KV head, as the decode kernel has them. Every
# other row is a prompt's chunk and is walked in wide tiles:
# RAGGED_WIDE_TILE queries, so that one KV head's pass (tile × G rows)
# fills the MXU's 128 rows several times over, cut down where the float32
# score tile (rows × page_size) would pass RAGGED_SCORE_BYTES. There is no
# tile between the two: each static width is a third of what the kernel
# costs to trace and lower at every start (PERF.md §6, PR 34), and only a
# chunk's tail of 2-8 queries (one admission in seventy) would walk faster
# in a narrow one.
RAGGED_WIDE_TILE = 128
RAGGED_SCORE_BYTES = 512 << 10


def ragged_tile_plan(groups: int, page_size: int) -> dict:
    """The ragged kernel's tiling for a layout (G = Hq / Hkv and the page
    size): static per engine, so it is a /debug/status entry, and the
    scheduler counts from ``wide_from`` which rows take the wide tile."""
    wide = min(RAGGED_WIDE_TILE, RAGGED_SCORE_BYTES // (4 * groups * page_size))
    wide = max(wide // 8, 1) * 8
    return {"narrow_tile": 1, "wide_tile": wide, "wide_from": 2,
            "rows_per_pass": wide * groups}


# One static width's scratch in the ragged kernel, rows = width · G: the
# tile's queries (Hkv, rows, D), a page's scores and probabilities (Hkv,
# rows, page_size), the running maximum and sum (Hkv, rows, 1), the running
# output (Hkv, rows, D), each row's absolute position (rows, 1).
_TileScratch = collections.namedtuple("_TileScratch", "q s p m l acc qpos")


def _ragged_paged_attn_kernel(
    # scalar prefetch
    page_table_ref,  # (R, max_pages) SMEM
    descr_ref,  # (R, 3) SMEM: q_start, q_len, kv_len per row; (R, 4) with the window operand
    # inputs
    q_ref,  # (T + slack, Hq, D) VMEM — whole packed batch (+ one tile of slack)
    k_pages_hbm,  # (P, page_size, Hkv*D) ANY/HBM
    v_pages_hbm,
    # output
    out_ref,  # (T + slack, Hq, D) VMEM
    # scratch
    k_buf,  # (2, page_size, pad128(Hkv*D)) VMEM
    v_buf,
    sems,  # DMA semaphores (2, 2)
    chain_ref,  # (2,) SMEM: is this row's first page in flight, and in which buffer
    *scratch,  # a _TileScratch's refs per width of ``widths``
    page_size: int,
    num_kv_heads: int,
    groups: int,
    head_dim: int,
    window: int | None,
    window_operand: bool,
    widths: tuple[int, ...],  # (1, the wide tile); (1,) where the batch holds one query
):
    """Grid over rows; each instance flash-attends its row's packed query
    span against the row's pages, one query tile at a time.

    Tiling scheme:
    - The tile is chosen per row from its ``q_len`` (ISSUE 34): a row of
      one query is one tile of one, any other row is walked in wide
      tiles (``widths``). There is ONE walk, tiles over pages, whose
      scalars follow the row's width; only what touches a tile's vectors
      (staging its queries, a page's visit, writing it out) exists once
      per static width, behind a ``pl.when`` on the row's. Each tile reads
      every page up to its causal horizon (and from its window's first
      page) once.
    - The packed query axis is NOT blocked by the grid — the whole batch
      (plus one tile of slack so a tile never clamps at the buffer edge)
      sits in VMEM and rows address their spans with dynamic slices from
      the prefetched descriptors. Mixed-step batches are budget-bounded
      (hundreds of tokens), so this is a few MiB, not a cache.
    - A tile's queries are laid out once, before its page walk, as one
      (tile·G, D) slab per KV head; scores, probabilities, the running
      maximum, sum and output live in scratch in the same order. A page's
      visit is a loop over heads for the scores, ONE flash update over
      every head's scores, and a loop over heads for the second product;
      the loops are traced once. The operands of both products reach the
      MXU in the dtype they are stored in, the probabilities in V's — the
      arithmetic of ``ragged_paged_attention_jax``.
    - The window is static (``window``: a model whose layers are alike
      pays no mask without one) or the fourth descriptor column
      (``window_operand``: a model that mixes windowed and global layers
      traces ONE instance, a global layer handing GLOBAL_WINDOW).
    - Page scratch is lane-padded: a misaligned folded axis (Hkv·D not a
      multiple of 128) DMAs into the valid prefix of a 128-aligned
      buffer; per-head compute slices stay inside the valid columns.
    - The double-buffered page DMA is one chain over the whole launch:
      a tile's last page starts the next tile's first, a row's last page
      the next active row's first (``chain_ref`` carries that across grid
      steps, which run in order).
    - Query tiles beyond a row's q_len are masked, and their output
      lanes preserve-and-defer: each row read-modify-writes its tile
      window, grid iterations are sequential, and every valid packed
      position is written exactly once by its owning row.
    """
    r = pl.program_id(0)
    R = pl.num_programs(0)
    scale = head_dim ** -0.5
    Hkv, G, D = num_kv_heads, groups, head_dim
    folded = k_pages_hbm.shape[-1]
    # The DMA'd page index is clamped for safety (matches the decode
    # kernel): inactive descriptor rows may carry stale table entries.
    num_pages_total = k_pages_hbm.shape[0]
    n = len(_TileScratch._fields)
    scratch = {W: _TileScratch(*scratch[i * n:(i + 1) * n]) for i, W in enumerate(widths)}

    @pl.when(r == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
        chain_ref[0] = 0
        chain_ref[1] = 0

    q_start = descr_ref[r, 0]
    q_len = descr_ref[r, 1]
    kv_len = descr_ref[r, 2]
    kv_start = kv_len - q_len  # absolute position of the row's first query
    if window_operand:
        window = descr_ref[r, 3]
    # The row's tile width, and whatever a tile does to vectors at it.
    W = jax.lax.select(q_len > 1, jnp.int32(widths[-1]), jnp.int32(1))

    def at_width(fn, *args):
        for Ws in widths:
            pl.when(W == Ws)(functools.partial(fn, Ws, scratch[Ws], *args))

    each_head = functools.partial(_each_head, Hkv, D)

    def page_dma(buf_slot, row, page_pos):
        page_idx = jnp.clip(page_table_ref[row, page_pos], 0, num_pages_total - 1)
        return (pltpu.make_async_copy(k_pages_hbm.at[page_idx],
                                      _page_dst(k_buf, buf_slot, folded),
                                      sems.at[buf_slot, 0]),
                pltpu.make_async_copy(v_pages_hbm.at[page_idx],
                                      _page_dst(v_buf, buf_slot, folded),
                                      sems.at[buf_slot, 1]))

    def first_page(row, q0):
        """Earliest page the tile that starts at ``row``'s q0-th query can
        see: its first query's window start (later queries see later
        keys only)."""
        if window is None:
            return jnp.int32(0)
        first_pos = descr_ref[row, 2] - descr_ref[row, 1] + q0
        return _div(jnp.maximum(first_pos + 1 - window, 0), page_size)

    def stage_tile(Ws, t, c, tile0):
        rows = Ws * G
        q_tile = q_ref[pl.dslice(tile0, Ws)]  # (Ws, Hq, D)
        for h in range(Hkv):
            t.q[h] = q_tile[:, h * G:(h + 1) * G, :].reshape(rows, D).astype(t.q.dtype)
        t.m[...] = jnp.full(t.m.shape, NEG_INF, jnp.float32)
        t.l[...] = jnp.zeros(t.l.shape, jnp.float32)
        t.acc[...] = jnp.zeros(t.acc.shape, jnp.float32)
        # Per-row absolute positions, expanded per group so the (Ws·G,
        # page_size) score mask indexes naturally; a query past the row's
        # last sees no key.
        qrow = c * Ws + _div(jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), G)
        t.qpos[...] = jnp.where(qrow < q_len, kv_start + qrow, -1)

    def visit(Ws, t, p, par):
        def score(h):
            k_h = k_buf[par, :, pl.ds(h * D, D)]  # (ps, D)
            t.s[h] = jax.lax.dot_general(
                t.q[h], k_h.astype(t.q.dtype), dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (rows, ps)

        each_head(score)
        # A query's own position bounds its keys (and is under kv_len).
        token_pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        qpos = t.qpos[...]  # (rows, 1)
        valid = token_pos <= qpos
        if window is not None:
            valid = valid & (token_pos > qpos - window)
        # Every KV head's scores under ONE flash update: the same vector
        # work as a head at a time, in one chain of dependent steps.
        s = jnp.where(valid, t.s[...] * scale, NEG_INF)  # (Hkv, rows, ps)
        m = t.m[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(s - m_new)
        t.l[...] = t.l[...] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        t.m[...] = m_new
        t.acc[...] = t.acc[...] * alpha
        t.p[...] = probs.astype(t.p.dtype)

        def weigh(h):
            v_h = v_buf[par, :, pl.ds(h * D, D)]  # (ps, D)
            t.acc[h] += jnp.dot(t.p[h], v_h, preferred_element_type=jnp.float32)

        each_head(weigh)

    def finish_tile(Ws, t, c, tile0):
        out = (t.acc[...] / jnp.maximum(t.l[...], 1e-20)).astype(out_ref.dtype)  # (Hkv, rows, D)
        tile = pl.dslice(tile0, Ws)
        theirs = out_ref[tile]  # what the tile's tail holds belongs to the rows after this one
        for h in range(Hkv):
            out_ref[tile, h * G:(h + 1) * G, :] = out[h].reshape(Ws, G, D)
        if Ws > 1:
            valid_q = (c * Ws + jax.lax.broadcasted_iota(jnp.int32, (Ws, 1, 1), 0)) < q_len
            out_ref[tile] = jnp.where(valid_q, out_ref[tile], theirs)

    @pl.when(q_len > 0)
    def _row():
        n_tiles = jax.lax.div(q_len + W - 1, W)
        # Whose first page the row's last page starts: the next row with
        # queries (R when none is left).
        nxt = jax.lax.while_loop(
            lambda i: (i < R) & (descr_ref[jnp.minimum(i, R - 1), 1] == 0),
            lambda i: i + 1, r + 1)
        nxt_row = jnp.minimum(nxt, R - 1)

        @pl.when(chain_ref[0] == 0)
        def _():
            for dma in page_dma(0, r, first_page(r, 0)):
                dma.start()

        def tile_body(c, par):
            tile0 = q_start + c * W
            at_width(stage_tile, c, tile0)
            # The tile's causal horizon bounds its page walk: queries in
            # tile c see keys < kv_start + (c+1)·W, so later pages are
            # fully masked and need not be DMA'd — the walk covers the
            # causal triangle, not the full rectangle.
            tile_kv = jnp.minimum(kv_start + (c + 1) * W, kv_len)
            n_pages_t = _div(tile_kv + page_size - 1, page_size)
            # Where the chain goes after this tile's last page: the row's
            # next tile's first page, else the next active row's.
            more_tiles = c + 1 < n_tiles
            chain_on = more_tiles | (nxt < R)
            after_row = jnp.where(more_tiles, r, nxt_row)
            after_page = jnp.where(more_tiles, first_page(r, (c + 1) * W),
                                   first_page(nxt_row, 0))

            def page_body(p, par):
                in_tile = p + 1 < n_pages_t

                @pl.when(in_tile | chain_on)
                def _():
                    for dma in page_dma(1 - par, jnp.where(in_tile, r, after_row),
                                        jnp.where(in_tile, p + 1, after_page)):
                        dma.start()

                for dma in page_dma(par, r, p):
                    dma.wait()
                at_width(visit, p, par)
                return 1 - par

            par = jax.lax.fori_loop(first_page(r, c * W), n_pages_t, page_body, par)
            at_width(finish_tile, c, tile0)
            return par

        par = jax.lax.fori_loop(0, n_tiles, tile_body,
                                jnp.where(chain_ref[0] == 1, chain_ref[1], 0))
        chain_ref[0] = (nxt < R).astype(jnp.int32)
        chain_ref[1] = par


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "interpret", "window"))
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
    window_dyn: jnp.ndarray | None = None,  # int32 scalar: the window as an operand
) -> jnp.ndarray:
    T, Hq, D = q.shape
    P, page_size, HkvD = k_pages.shape
    R = page_table.shape[0]
    G = Hq // num_kv_heads
    assert window is None or window_dyn is None, "one window: static, or the operand"
    # The wide tile, no wider than the batch (in eights).
    tile = min(ragged_tile_plan(G, page_size)["wide_tile"], -(-T // 8) * 8)
    widths = (1, tile) if T > 1 else (1,)
    # One tile of slack so a row's last tile never clamps at the buffer
    # edge (a clamped dynamic slice would shift the tile window off the
    # mask's indexing). Sliced back off below.
    qp = jnp.pad(q, ((0, tile), (0, 0), (0, 0)))
    # The products' operand dtype: what q and the pages are stored in.
    mxu_dtype = jnp.promote_types(q.dtype, k_pages.dtype)

    kernel = functools.partial(
        _ragged_paged_attn_kernel,
        page_size=page_size,
        num_kv_heads=num_kv_heads,
        groups=G,
        head_dim=D,
        window=window,
        window_operand=window_dyn is not None,
        widths=widths,
    )
    descr = [q_starts, q_lens, kv_lens]
    if window_dyn is not None:
        descr.append(jnp.broadcast_to(window_dyn, q_lens.shape))
    descr = jnp.stack(descr, axis=1).astype(jnp.int32)
    scratch_shapes = [
        pltpu.VMEM((2, page_size, _pad_lanes(HkvD)), k_pages.dtype),
        pltpu.VMEM((2, page_size, _pad_lanes(HkvD)), v_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((2,), jnp.int32),
    ]
    for W in widths:
        rows = W * G
        scratch_shapes += _TileScratch(
            q=pltpu.VMEM((num_kv_heads, rows, D), mxu_dtype),
            s=pltpu.VMEM((num_kv_heads, rows, page_size), jnp.float32),
            p=pltpu.VMEM((num_kv_heads, rows, page_size), v_pages.dtype),
            m=pltpu.VMEM((num_kv_heads, rows, 1), jnp.float32),
            l=pltpu.VMEM((num_kv_heads, rows, 1), jnp.float32),
            acc=pltpu.VMEM((num_kv_heads, rows, D), jnp.float32),
            qpos=pltpu.VMEM((rows, 1), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((T + tile, Hq, D), lambda r, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((T + tile, Hq, D), lambda r, *_: (0, 0, 0)),
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + tile, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(page_table.astype(jnp.int32), descr, qp, k_pages, v_pages)
    return out[:T]


def ragged_paged_attention_sharded(q, k_pages, v_pages, page_table, q_starts, q_lens,
                                   kv_lens, num_kv_heads: int, mesh,
                                   window: int | None = None,
                                   interpret: bool | None = None,
                                   replicated: bool = False, window_dyn=None) -> jnp.ndarray:
    """Ragged kernel under a mesh: kv-head-local per tp shard (no
    collectives, same layout algebra as paged_attention_sharded), or
    fully replicated for tp=1 meshes / non-tp-divisible heads.
    Descriptors, the page table and the window operand are replicated
    host metadata."""
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = not on_tpu()
    hkv_local = num_kv_heads if replicated else num_kv_heads // mesh.shape["tp"]

    def local(q_l, k_l, v_l, pt_l, qs_l, ql_l, kl_l, *w_l):
        return ragged_paged_attention_tpu(q_l, k_l, v_l, pt_l, qs_l, ql_l, kl_l,
                                          hkv_local, interpret=interpret,
                                          window=window, window_dyn=w_l[0] if w_l else None)

    rep = P()
    dyn = () if window_dyn is None else (jnp.asarray(window_dyn, jnp.int32),)
    if replicated:
        in_specs = (rep,) * (7 + len(dyn))
        out_spec = rep
    else:
        in_specs = (P(None, "tp", None), P(None, None, "tp"), P(None, None, "tp"),
                    rep, rep, rep, rep) + (rep,) * len(dyn)
        out_spec = P(None, "tp", None)
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_spec, check_vma=False,
    )(q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens, *dyn)


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
                    mesh=None, window_dyn=None) -> jnp.ndarray:
    """Dispatch (see paged_dispatch): Pallas kernel on single-device
    TPU, shard_mapped over ``tp`` under a mesh (kv-head-local), or a
    replicated shard_map launch for tp=1 meshes / non-tp-divisible
    heads; the XLA gather path only off-TPU (~10.6× slower at serving
    shape). ``IG_TPU_PAGED_KERNEL=1/0`` forces the kernel choice (tests
    exercise the shard_map path on a CPU mesh in interpret mode). The
    flag is captured at import (module attr FORCE_PAGED_KERNEL) —
    jitted forwards bake the dispatch into the trace, so a mid-session
    env flip would not apply to compiled shapes (advisor round-2).
    ``window`` is static; ``window_dyn`` is the window as an int32 scalar
    operand (GLOBAL_WINDOW for none), for a model whose layers differ."""
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
                                       replicated=path == "kernel_replicated",
                                       window_dyn=window_dyn)
    if path in ("kernel", "kernel_sharded", "kernel_replicated"):
        return paged_attention_tpu(q, k_pages, v_pages, page_table, lengths, num_kv_heads,
                                   window=window, interpret=interpret, window_dyn=window_dyn)
    return paged_attention_jax(q, k_pages, v_pages, page_table, lengths, num_kv_heads,
                               window=window if window_dyn is None else window_dyn)


def ragged_paged_attention(q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens,
                           num_kv_heads: int, window: int | None = None,
                           mesh=None, window_dyn=None) -> jnp.ndarray:
    """Dispatch for the mixed-batch ragged op (ISSUE 12): same decision
    table as ``paged_attention`` (paged_dispatch is the single source),
    applied to the ragged kernel/reference pair. The pure-JAX ragged
    reference is the correctness twin and the only non-TPU path.
    ``window`` is static; ``window_dyn`` is the window as an int32 scalar
    operand (GLOBAL_WINDOW for none), for a model whose layers differ."""
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
            replicated=path == "kernel_replicated", window_dyn=window_dyn)
    if path in ("kernel", "kernel_sharded", "kernel_replicated"):
        return ragged_paged_attention_tpu(
            q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens,
            num_kv_heads, interpret=interpret, window=window, window_dyn=window_dyn)
    return ragged_paged_attention_jax(
        q, k_pages, v_pages, page_table, q_starts, q_lens, kv_lens,
        num_kv_heads, window=window if window_dyn is None else window_dyn)
