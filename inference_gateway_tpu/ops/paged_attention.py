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
    mask: jnp.ndarray | None = None,  # (B, max_pages * page_size) bool: of what a slot sees, what it attends
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
    if mask is not None:
        valid = valid & mask
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


def _visit_dmas(k_pages_hbm, v_pages_hbm, k_buf, v_buf, sems):
    """A visit's page copies, for both paged kernels: ``start(par, idx, i)``
    starts the copy of pool page ``idx`` (K and V) into slot ``i`` of half
    ``par`` of the (2, N, page_size, lanes) buffers, filling only the valid
    folded columns of the padded buffer; ``arrived(par, n)`` waits for ``n``
    of them."""
    folded = k_pages_hbm.shape[-1]

    def page_dma(par, idx, i):
        return (pltpu.make_async_copy(k_pages_hbm.at[idx], _page_dst(k_buf.at[par], i, folded),
                                      sems.at[par, 0]),
                pltpu.make_async_copy(v_pages_hbm.at[idx], _page_dst(v_buf.at[par], i, folded),
                                      sems.at[par, 1]))

    def start(par, idx, i):
        for dma in page_dma(par, idx, i):
            dma.start()

    def arrived(par, n):
        def one(i, c):
            for dma in page_dma(par, 0, i):  # a wait counts bytes: any page's
                dma.wait()
            return c

        jax.lax.fori_loop(jnp.int32(0), n, one, 0)

    return start, arrived


def _paged_attn_kernel(
    # scalar prefetch
    page_table_ref,  # (B + 1, max_pages) SMEM
    length_ref,  # (B + 1,) SMEM: the slots' lengths, -1; then the window where it is an operand
    # inputs
    q_ref,  # (B, Hkv, G, D) VMEM: every slot's query, by KV head
    k_pages_hbm,  # (P, page_size, Hkv*D) in ANY/HBM
    v_pages_hbm,
    *refs,  # where ``masked``, first the mask: (B, tokens) int32 VMEM, a slot's row by position; then:
    window: int | None,
    window_operand: bool,
    masked: bool = False,
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
    traces ONE instance, a global layer handing GLOBAL_WINDOW).

    ``masked`` (ISSUE 45): of what a slot sees it attends only the positions
    its row of the mask holds non-zero (a learned sparse attention's chosen
    set; at least one, or the row's output means nothing). Every page the
    slot holds is still walked whole: a visit's lanes of the mask are and-ed
    into what it may see, and a visit that attends nothing before the first
    that does is rescaled away by that one."""
    mask_ref, *refs = refs if masked else (None, *refs)
    (out_ref,  # output: (B, Hkv, G, D) VMEM; the rest is scratch
     k_buf,  # (2, N, page_size, pad128(Hkv*D)) VMEM — a page's DMA fills [:Hkv*D]
     v_buf,
     sems,  # DMA semaphores (2, 2)
     s_ref,  # (Hkv, G, N·page_size) float32: a visit's scores
     p_ref,  # the same in V's dtype: its probabilities
     m_ref,  # (Hkv, G, 1) float32: the slot's running maximum,
     l_ref,  # sum
     acc_ref,  # (Hkv, G, D) and output
     ) = refs
    B, Hkv, G, D = q_ref.shape
    _, N, page_size, _ = k_buf.shape
    scale = D ** -0.5
    last_page = k_pages_hbm.shape[0] - 1
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

    start, arrived = _visit_dmas(k_pages_hbm, v_pages_hbm, k_buf, v_buf, sems)

    def fetch(par, s, page, n):
        """Start the DMAs of ``n`` pages of slot ``s`` from ``page`` on."""
        def one(i, c):
            # Clamped: a stale table entry must not take the DMA out of bounds.
            start(par, jnp.minimum(jnp.maximum(page_table_ref[s, page + i], 0), last_page), i)
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
            if masked:
                at = (p0 + done) * page_size
                if page_size % LANE == 0:
                    at = pl.multiple_of(at, LANE)
                valid = valid & (mask_ref[pl.ds(s, 1), pl.ds(at, N * page_size)] != 0)[None]
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


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "interpret", "window", "pages_per_visit", "name"))
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
    mask: jnp.ndarray | None = None,  # (B, max_pages * page_size) bool: of what a slot sees, what it attends
    name: str | None = None,  # the kernel's in a trace, where the caller's layer is read by it
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
                               window_operand=window_dyn is not None, masked=mask is not None)
    # A slot more, of length -1: it ends the search for the next live slot and
    # holds no page. After it the window, where it is an operand.
    descr = jax.lax.pad(lengths.astype(jnp.int32), jnp.int32(-1), ((0, 1, 0),))
    if window_dyn is not None:
        descr = jnp.concatenate([descr, jnp.reshape(window_dyn, (1,)).astype(jnp.int32)])
    by_head = (B, num_kv_heads, G, D)
    tile = (num_kv_heads, G, N * page_size)
    masks, mask_specs = (), []
    if mask is not None:
        # A slot's row in the lanes, a visit more than the table holds: a visit
        # reads N pages' lanes from its first page, wherever the walk began.
        row = (B, (page_table.shape[1] + N) * page_size)
        masks = (jax.lax.pad(mask.astype(jnp.int32), jnp.int32(0), ((0, 0, 0), (0, N * page_size, 0))),)
        mask_specs = [pl.BlockSpec(row, lambda i, *_: (0, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(by_head, lambda i, *_: (0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
            *mask_specs,
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
        name=name,
    )(jax.lax.pad(page_table.astype(jnp.int32), jnp.int32(0), ((0, 1, 0), (0, 0, 0))),
      descr, q.reshape(by_head).astype(mxu_dtype), k_pages, v_pages, *masks)
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


# The ragged kernel's query tiles (ISSUE 34, ISSUE 44). A row's queries are
# walked against its pages in tiles, and a tile reads each page it can see
# once — so the width of the tile is how often a row's context is read and how
# many rows one MXU pass carries. There is ONE static width, the wide tile:
# RAGGED_WIDE_TILE queries, so that one KV head's pass (tile × G rows) fills
# the MXU's 128 rows several times over, cut down where the float32 score tile
# of one page (rows × page_size) would pass RAGGED_SCORE_BYTES. A row of ONE
# query (a decode row) is not the ragged kernel's: the launch hands the
# one-query rows of the packed prefix to the decode kernel, which walks a
# slot's pages several a visit (``ragged_paged_attention_tpu``). A second
# static width is a third more of what the kernel costs to trace and lower at
# every start (PERF.md §6, PR 34), and only a chunk's tail of 2-8 queries (one
# admission in seventy) would walk faster in a narrow one.
#
# A wide tile's VISIT is up to N consecutive pages, scored, soft-maxed and
# weighed as one (rows, N·page_size) tile a KV head: the running maximum, sum
# and output are rescaled once a visit, and a lane reduction serves N pages
# (ISSUE 44: at Trinity-Mini's shape a page costs 4.7 us at N = 1, 3.1 at 2,
# 2.3 at 4; PERF.md §5). N is what RAGGED_VISIT_BYTES holds of a page's
# buffers (K and V, each double-buffered) and its float32 scores and
# probabilities, and a RAGGED_VISITS_A_ROW-th of the longest row at the most:
# a walk's last visit pays for the whole tile, so where rows are short (a
# chat prompt's tile sees one to three pages) a wide visit is mostly masked
# columns (4 pages a visit are a quarter slower than 2 at the chat shapes).
RAGGED_WIDE_TILE = 128
RAGGED_SCORE_BYTES = 512 << 10
RAGGED_VISIT_BYTES = 16 << 20
RAGGED_VISITS_A_ROW = 8
# A visit whose pages lie wholly inside every query's view (behind the tile's
# first query, inside the window of its last) is not masked (ISSUE 44);
# hack/ragged_attention_bench.py switches that off for its table.
RAGGED_UNMASKED_VISITS = True


def ragged_tile_plan(num_q_heads: int, num_kv_heads: int, head_dim: int, page_size: int,
                     max_pages: int, dtype=jnp.bfloat16) -> dict:
    """The mixed step's attention for a layout: static per engine, so it is a
    /debug/status entry (``ragged_attention``), and the scheduler counts from
    it which rows take the wide tile and the pages their tiles walk."""
    groups = num_q_heads // num_kv_heads
    itemsize = jnp.dtype(dtype).itemsize
    wide = min(RAGGED_WIDE_TILE, RAGGED_SCORE_BYTES // (4 * groups * page_size))
    wide = max(wide // 8, 1) * 8
    rows = wide * groups
    page = page_size * _pad_lanes(num_kv_heads * head_dim) * itemsize
    tile = num_kv_heads * rows * page_size * (4 + itemsize)  # a page's scores and probabilities
    visit = max(1, min(RAGGED_VISIT_BYTES // (2 * 2 * page + tile), max_pages // RAGGED_VISITS_A_ROW))
    decode = decode_walk_plan(num_q_heads, num_kv_heads, head_dim, page_size, max_pages, dtype)
    return {"wide_tile": wide, "wide_from": 2, "rows_per_pass": rows,
            "wide_pages_per_visit": visit, "unmasked_visits": RAGGED_UNMASKED_VISITS,
            "one_query_rows": "decode_kernel",
            "one_query_pages_per_visit": decode["pages_per_visit"]}


def ragged_walk_pages(rows, windows, plan: dict, page_size: int) -> tuple[int, int]:
    """(pages walked, pages under the mask) by the wide tiles of one mixed
    step, from the scalars the kernel branches on: ``rows`` the (q_len,
    kv_len) of the rows that take the wide tile, ``windows`` the step's
    attention layers as (window or None, layers). Host arithmetic for the
    timeline's ``attn_pages_walked`` / ``attn_pages_masked``."""
    W, N = plan["wide_tile"], plan["wide_pages_per_visit"]
    walked = masked = 0
    for q_len, kv_len in rows:
        for start in range(kv_len - q_len, kv_len, W):  # a tile's first query's position
            end = min(start + W, kv_len)
            last = -(-end // page_size)
            for window, layers in windows:
                first = max(start + 1 - window, 0) // page_size if window else 0
                for p0 in range(first // N * N, last, N):
                    pages = layers * (min(p0 + N, last) - max(p0, first))
                    walked += pages
                    if not plan["unmasked_visits"] or _edge_visit(p0, N, page_size, start, end, window):
                        masked += pages
    return walked, masked


def _edge_visit(p0, n: int, page_size: int, start, end, window):
    """Does the visit of pages p0..p0+n hold a key that some query of the
    tile (positions start..end-1) may not see: one past the tile's first
    query, or one at or before its last query's window start. Every other
    visit needs no mask. (Scalars in the kernel, ints on the host.)"""
    edge = (p0 + n) * page_size - 1 > start
    if window is not None:
        edge = edge | (p0 * page_size <= end - 1 - window)
    return edge


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
    k_buf,  # (2, N, page_size, pad128(Hkv*D)) VMEM — a page's DMA fills [:Hkv*D]
    v_buf,
    sems,  # DMA semaphores (2, 2)
    chain_ref,  # (2,) SMEM: is this row's first visit in flight, and in which buffer
    q_s,  # (Hkv, rows, D): the tile's queries, rows = tile · G
    s_s,  # (Hkv, rows, N·page_size) float32: a visit's scores
    p_s,  # the same in V's dtype: its probabilities
    m_s,  # (Hkv, rows, 1) float32: the running maximum,
    l_s,  # sum,
    a_s,  # and what a visit rescales them by
    acc_s,  # (Hkv, rows, D) float32: the running output
    qpos_s,  # (rows, 1) int32: each row's absolute position
    *,
    tile: int,
    groups: int,
    head_dim: int,
    window: int | None,
    window_operand: bool,
    unmasked: bool,
):
    """Grid over rows; each instance flash-attends its row's packed query
    span against the row's pages, one query tile at a time.

    Tiling scheme:
    - Every row is walked in tiles of ``tile`` queries (ISSUE 34; a row of
      one query reaches this kernel only from outside the packed prefix,
      and is a tile of which one query is live). Each tile reads every
      page up to its causal horizon (and from its window's first page) once.
    - The packed query axis is NOT blocked by the grid — the whole batch
      (plus one tile of slack so a tile never clamps at the buffer edge)
      sits in VMEM and rows address their spans with dynamic slices from
      the prefetched descriptors. Mixed-step batches are budget-bounded
      (hundreds of tokens), so this is a few MiB, not a cache.
    - A tile's queries are laid out once, before its page walk, as one
      (tile·G, D) slab per KV head; scores, probabilities, the running
      maximum, sum and output live in scratch in the same order. A VISIT
      is up to N consecutive pages (ISSUE 44): a loop over heads for the
      scores, ONE flash update over every head's (rows, N·page_size)
      scores, and a loop over heads for the second product, which rescales
      the running output as it adds to it; the loops are traced once. The
      operands of both products reach the MXU in the dtype they are stored
      in, the probabilities in V's — the arithmetic of
      ``ragged_paged_attention_jax``. Visits begin at multiples of N
      (``aligned``), so a walk's first and last visit are ragged: only
      their live pages are fetched, the rest of the buffer lies before the
      tile's window or past its horizon and is masked (V's buffer starts as
      zeros, so a masked column never weighs a NaN).
    - A visit is masked only where it can hold a key some query may not
      see (``_edge_visit``): the one or two visits on the tile's diagonal,
      the one at its window's near edge; the mask is a pass of its own over
      the scores, under ``pl.when``. Every other visit has no iota, compare
      or select. A tile's tail rows (past the row's last query) come out
      of such a visit as if live, and are never written (``finish_tile``).
    - The window is static (``window``: a model whose layers are alike
      pays no mask without one) or the fourth descriptor column
      (``window_operand``: a model that mixes windowed and global layers
      traces ONE instance, a global layer handing GLOBAL_WINDOW).
    - Page scratch is lane-padded: a misaligned folded axis (Hkv·D not a
      multiple of 128) DMAs into the valid prefix of a 128-aligned
      buffer; per-head compute slices stay inside the valid columns.
    - The double-buffered page DMA is one chain over the whole launch:
      a visit starts the next one's pages, a tile's last visit the next
      tile's first, a row's last the next active row's first
      (``chain_ref`` carries that across grid steps, which run in order).
    - Query tiles beyond a row's q_len are masked, and their output
      lanes preserve-and-defer: each row read-modify-writes its tile
      window, grid iterations are sequential, and every valid packed
      position is written exactly once by its owning row.
    """
    r = pl.program_id(0)
    R = pl.num_programs(0)
    Hkv, rows, D = q_s.shape
    _, N, page_size, _ = k_buf.shape
    G, W = groups, tile
    scale = head_dim ** -0.5
    # The DMA'd page index is clamped for safety (matches the decode
    # kernel): inactive descriptor rows may carry stale table entries.
    last_page = k_pages_hbm.shape[0] - 1
    zero = jnp.int32(0)

    @pl.when(r == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        chain_ref[0] = 0
        chain_ref[1] = 0

    q_start = descr_ref[r, 0]
    q_len = descr_ref[r, 1]
    if window_operand:
        window = descr_ref[r, 3]

    each_head = functools.partial(_each_head, Hkv, D)

    start, arrived = _visit_dmas(k_pages_hbm, v_pages_hbm, k_buf, v_buf, sems)

    def live(p0, first, last):
        """(first page, pages) that the visit of pages p0..p0+N fetches of a
        walk first..last: its first visit may begin, and its last end, inside
        the visit; what the buffer holds beside them is masked."""
        lo = jnp.maximum(p0, first)
        return lo, jnp.minimum(p0 + N, last) - lo

    def fetch(par, row, p0, first, last):
        """Start the DMAs of that visit of ``row``, page p0 + i into slot i."""
        lo, n = live(p0, first, last)

        def one(i, c):
            start(par, jnp.minimum(jnp.maximum(page_table_ref[row, lo + i], 0), last_page), lo - p0 + i)
            return c

        jax.lax.fori_loop(zero, n, one, 0)

    def aligned(page):
        """The visit that holds ``page``: visits begin at multiples of N,
        whatever tile walks them, so a query's keys fall into the same visits
        (and its sums run in the same order) however its prompt was chunked:
        a prompt adopted from the prefix cache answers bit for bit as it did
        when it was computed whole."""
        return _div(page, N) * N

    def walk(row, c):
        """(first page, page after the last, first query's position, position
        after the last query's) of tile ``c`` of ``row``: queries of the tile
        see keys before its horizon, so later pages are fully masked and need
        not be DMA'd — the walk covers the causal triangle, not the rectangle;
        and from its first query's window start (later queries see later keys
        only)."""
        kv_len = descr_ref[row, 2]
        start = kv_len - descr_ref[row, 1] + c * W
        end = jnp.minimum(start + W, kv_len)
        last = _div(end + page_size - 1, page_size)
        if window is None:
            return zero, last, start, end
        return _div(jnp.maximum(start + 1 - window, 0), page_size), last, start, end

    def stage_tile(c, tile0):
        q_tile = q_ref[pl.dslice(tile0, W)]  # (W, Hq, D)
        for h in range(Hkv):
            q_s[h] = q_tile[:, h * G:(h + 1) * G, :].reshape(rows, D).astype(q_s.dtype)
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
        # Per-row absolute positions, expanded per group so the (W·G,
        # N·page_size) score mask indexes naturally; a query past the row's
        # last sees no key.
        qrow = c * W + _div(jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), G)
        qpos_s[...] = jnp.where(qrow < q_len, descr_ref[r, 2] - q_len + qrow, -1)

    def mask(p0):
        """A query's own position bounds its keys (and is under kv_len)."""
        token_pos = p0 * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, N * page_size), 1)
        qpos = qpos_s[...]  # (rows, 1)
        valid = token_pos <= qpos
        if window is not None:
            valid = valid & (token_pos > qpos - window)
        s_s[...] = jnp.where(valid, s_s[...], NEG_INF)

    def visit(p0, par, edge):
        def score(h):
            k_h = k_buf[par, :, :, pl.ds(h * D, D)].reshape(N * page_size, D)
            s_s[h] = scale * jax.lax.dot_general(
                q_s[h], k_h.astype(q_s.dtype), dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (rows, N·ps)

        each_head(score)
        # The mask is a pass of its own, and the only thing under the branch:
        # the whole visit in two bodies, masked and not, ran a quarter slower.
        pl.when(edge)(functools.partial(mask, p0))
        # Every KV head's scores under ONE flash update: the same vector
        # work as a head at a time, in one chain of dependent steps.
        s = s_s[...]  # (Hkv, rows, N·ps)
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(s - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        m_s[...] = m_new
        a_s[...] = alpha
        p_s[...] = probs.astype(p_s.dtype)

        def weigh(h):
            v_h = v_buf[par, :, :, pl.ds(h * D, D)].reshape(N * page_size, D)
            acc_s[h] = acc_s[h] * a_s[h] + jnp.dot(p_s[h], v_h, preferred_element_type=jnp.float32)

        each_head(weigh)

    def finish_tile(c, tile0):
        out = (acc_s[...] / jnp.maximum(l_s[...], 1e-20)).astype(out_ref.dtype)  # (Hkv, rows, D)
        span = pl.dslice(tile0, W)
        theirs = out_ref[span]  # what the tile's tail holds belongs to the rows after this one
        for h in range(Hkv):
            out_ref[span, h * G:(h + 1) * G, :] = out[h].reshape(W, G, D)
        valid_q = (c * W + jax.lax.broadcasted_iota(jnp.int32, (W, 1, 1), 0)) < q_len
        out_ref[span] = jnp.where(valid_q, out_ref[span], theirs)

    @pl.when(q_len > 0)
    def _row():
        n_tiles = jax.lax.div(q_len + W - 1, W)
        # Whose first visit the row's last visit starts: the next row with
        # queries (R when none is left).
        nxt = jax.lax.while_loop(
            lambda i: (i < R) & (descr_ref[jnp.minimum(i, R - 1), 1] == 0),
            lambda i: i + 1, r + 1)
        nxt_row = jnp.minimum(nxt, R - 1)

        @pl.when(chain_ref[0] == 0)
        def _():
            first, last, _, _ = walk(r, 0)
            fetch(0, r, aligned(first), first, last)

        def tile_body(c, par):
            tile0 = q_start + c * W
            stage_tile(c, tile0)
            first, last, start, end = walk(r, c)
            # Where the chain goes after this tile's last visit: the row's
            # next tile's first pages, else the next active row's.
            more_tiles = c + 1 < n_tiles
            chain_on = more_tiles | (nxt < R)
            after_row = jnp.where(more_tiles, r, nxt_row)
            after_first, after_last, _, _ = walk(after_row, jnp.where(more_tiles, c + 1, 0))

            def visit_body(carry):
                p0, par = carry
                more = p0 + N < last  # the tile's walk goes on after this visit

                @pl.when(more | chain_on)
                def _():
                    fetch(1 - par, jnp.where(more, r, after_row), jnp.where(more, p0 + N, aligned(after_first)),
                          jnp.where(more, first, after_first), jnp.where(more, last, after_last))

                arrived(par, live(p0, first, last)[1])
                visit(p0, par, _edge_visit(p0, N, page_size, start, end, window) if unmasked else True)
                return p0 + N, 1 - par

            _, par = jax.lax.while_loop(lambda carry: carry[0] < last, visit_body, (aligned(first), par))
            finish_tile(c, tile0)
            return par

        par = jax.lax.fori_loop(0, n_tiles, tile_body,
                                jnp.where(chain_ref[0] == 1, chain_ref[1], 0))
        chain_ref[0] = (nxt < R).astype(jnp.int32)
        chain_ref[1] = par


def _one_query_prefix(page_table, q_starts, q_lens, kv_lens, n: int):
    """The one-query rows among the first ``n`` packed positions, BY packed
    position (the scheduler packs a step's decode rows first, so they are
    the packed prefix): each position's page-table row and length (0 where
    no one-query row sits), and which rows they are. No scatter: position i
    finds its row by comparison."""
    one = q_lens == 1
    hit = one[None, :] & (q_starts[None, :] == jnp.arange(n, dtype=q_starts.dtype)[:, None])  # (n, R)
    row = jnp.argmax(hit, axis=1)
    lengths = jnp.where(jnp.any(hit, axis=1), kv_lens[row], 0)
    return page_table[row], lengths, one & (q_starts < n)


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "interpret", "window", "pages_per_visit",
                                             "row_pages_per_visit"))
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
    pages_per_visit: int | None = None,  # a wide tile's: the layout's (ragged_tile_plan) unless a test says
    row_pages_per_visit: int | None = None,  # a one-query row's, likewise
) -> jnp.ndarray:
    """Two launches a call (ISSUE 44): the one-query rows of the packed
    prefix (a mixed step's decode rows) go to the decode kernel BY packed
    position, which walks their pages several a visit and skips a position
    with no such row; every other row to the ragged kernel, which sees the
    first as rows of no query. Each writes zeros where the other writes, so
    the two outputs are joined by one addition."""
    T, Hq, D = q.shape
    P, page_size, HkvD = k_pages.shape
    R, max_pages = page_table.shape
    G = Hq // num_kv_heads
    assert window is None or window_dyn is None, "one window: static, or the operand"
    plan = ragged_tile_plan(Hq, num_kv_heads, D, page_size, max_pages, k_pages.dtype)
    # The wide tile, no wider than the batch (in eights).
    tile = min(plan["wide_tile"], -(-T // 8) * 8)
    N = pages_per_visit or plan["wide_pages_per_visit"]
    rows = tile * G

    n = min(R, T)
    table_1, lengths_1, to_decode = _one_query_prefix(page_table, q_starts, q_lens, kv_lens, n)
    out_1 = paged_attention_tpu(q[:n], k_pages, v_pages, table_1, lengths_1, num_kv_heads,
                                interpret=interpret, window=window, window_dyn=window_dyn,
                                pages_per_visit=row_pages_per_visit)

    # One tile of slack so a row's last tile never clamps at the buffer
    # edge (a clamped dynamic slice would shift the tile window off the
    # mask's indexing). Sliced back off below.
    qp = jnp.pad(q, ((0, tile), (0, 0), (0, 0)))
    # The products' operand dtype: what q and the pages are stored in.
    mxu_dtype = jnp.promote_types(q.dtype, k_pages.dtype)

    kernel = functools.partial(
        _ragged_paged_attn_kernel, tile=tile, groups=G, head_dim=D, window=window,
        window_operand=window_dyn is not None, unmasked=RAGGED_UNMASKED_VISITS)
    descr = [q_starts, jnp.where(to_decode, 0, q_lens), kv_lens]
    if window_dyn is not None:
        descr.append(jnp.broadcast_to(window_dyn, q_lens.shape))
    descr = jnp.stack(descr, axis=1).astype(jnp.int32)
    by_head = (num_kv_heads, rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((T + tile, Hq, D), lambda r, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((T + tile, Hq, D), lambda r, *_: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, N, page_size, _pad_lanes(HkvD)), k_pages.dtype),
            pltpu.VMEM((2, N, page_size, _pad_lanes(HkvD)), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((*by_head, D), mxu_dtype),
            pltpu.VMEM((*by_head, N * page_size), jnp.float32),
            pltpu.VMEM((*by_head, N * page_size), v_pages.dtype),
            pltpu.VMEM((*by_head, 1), jnp.float32),
            pltpu.VMEM((*by_head, 1), jnp.float32),
            pltpu.VMEM((*by_head, 1), jnp.float32),
            pltpu.VMEM((*by_head, D), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + tile, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(page_table.astype(jnp.int32), descr, qp, k_pages, v_pages)
    return out[:T] + jnp.pad(out_1, ((0, T - n), (0, 0), (0, 0)))


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
                    mesh=None, window_dyn=None, mask=None, name: str | None = None) -> jnp.ndarray:
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
    operand (GLOBAL_WINDOW for none), for a model whose layers differ.
    ``mask`` (B, max_pages * page_size) bool: of what a slot sees, what it
    attends (no mesh: no model with one shards); ``name`` the kernel's in a
    trace."""
    force = FORCE_PAGED_KERNEL
    platform = device_platform()
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if mask is not None and mesh is not None:
        raise NotImplementedError("a masked paged attention under a mesh: the shard_map launches take no mask")
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
                                   window=window, interpret=interpret, window_dyn=window_dyn,
                                   mask=mask, name=name)
    return paged_attention_jax(q, k_pages, v_pages, page_table, lengths, num_kv_heads,
                               window=window if window_dyn is None else window_dyn, mask=mask)


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
