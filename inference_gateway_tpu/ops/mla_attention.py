"""Paged attention over LATENT pages (multi-head latent attention, ISSUE 47).

A latent attention caches, a token a layer, ONE row: the compressed
key-value latent c_kv (``value_dim`` values, after its norm) followed by the
rotary key k_rope every head shares. In the absorbed form a head's query is
carried into the latent's space (q_nope W_UK^T, beside its rotary part), so
every head scores against that one row, and the row's first ``value_dim``
values are also the value: multi-query attention with one key head whose
value is a prefix of its key. The pool is (pages, page_size, width), one
array; a page is fetched ONCE a visit and used for both products. ``width`` is
the row padded with zeros to the 128-lane grid (576 -> 640 at the published
sizes): the chip stores an array's minor axis in whole lane tiles whatever
its shape says, and a DMA may not slice inside one (Mosaic: "slice shape must
be aligned to tiling (128), but is 576"), so the padding is in the shape,
where every count of the pool's bytes sees it.

``mla_paged_attention``: one query a slot (the decode chunk).
``mla_ragged_attention``: a mixed step's packed rows; with ``pair`` (ISSUE 56,
a model that verifies its own draft) the rows of exactly TWO queries, a decoding
row's pending token and its draft, are a tile of two each under a third name,
``mla_pair_attn_kernel``: the two queries' heads are the rows of one product
against each visit of the row's pages, which are fetched once for both. As
``ops/paged_attention.ragged_paged_attention_tpu`` it hands the one-query rows
of the packed prefix to the decode launch by packed position; every other row
is cut into TILES of ``chunk_tile`` queries, laid out in packed order, and a
second launch of the same kernel walks each tile's pages up to its horizon.
Both launches are ``_mla_attn_kernel`` under the names
``mla_decode_attn_kernel`` and ``mla_chunk_attn_kernel``.

Off the TPU both are ``ops/paged_attention``'s pure-JAX twins with the pool as
keys and as values and one key head (the numerics oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from inference_gateway_tpu.ops.paged_attention import (
    DECODE_VISIT_BYTES,
    FORCE_PAGED_KERNEL,
    NEG_INF,
    RAGGED_SCORE_BYTES,
    RAGGED_WIDE_TILE,
    _div,
    _one_query_prefix,
    paged_attention_jax,
    paged_dispatch,
    ragged_paged_attention_jax,
)
from inference_gateway_tpu.utils.platform import device_platform

# Pages a chunk tile's visit holds: a tile's last visit is computed whole
# whatever part of it lies past the tile's horizon, so a wide visit wastes
# more of a short context than it saves in rescales.
CHUNK_PAGES_PER_VISIT = 4


def chunk_tile(num_heads: int, page_size: int) -> int:
    """Queries a chunk's tile holds: ``ragged_tile_plan``'s rule with every head
    on the one key row (the float32 scores of a page stay under RAGGED_SCORE_BYTES)."""
    return max(min(RAGGED_WIDE_TILE, RAGGED_SCORE_BYTES // (4 * num_heads * page_size)) // 8, 1) * 8


def mla_walk_plan(num_heads: int, width: int, page_size: int, max_pages: int, dtype=jnp.bfloat16) -> dict:
    """The two launches' walks for a layout, under ``ragged_tile_plan``'s
    keys (``ragged_walk_pages`` counts a step's pages from them: tiles from a
    row's first query, visits at multiples of N, a visit masked where it holds
    a key past the tile's first query): static per engine, /debug/status
    ``ragged_attention``."""
    itemsize = jnp.dtype(dtype).itemsize
    page = page_size * width * itemsize
    tile = chunk_tile(num_heads, page_size)
    one = num_heads * page_size * (4 + itemsize)  # a page's scores and probabilities, one query
    return {"wide_tile": tile, "wide_from": 2, "rows_per_pass": tile * num_heads,
            "wide_pages_per_visit": max(1, min(CHUNK_PAGES_PER_VISIT, max_pages)),
            "unmasked_visits": True, "one_query_rows": "mla_decode_attn_kernel",
            "one_query_pages_per_visit": max(1, min(DECODE_VISIT_BYTES // (2 * page + one), max_pages)),
            "pair_pages_per_visit": max(1, min(DECODE_VISIT_BYTES // (2 * page + 2 * one), max_pages)),
            "latent_fetched": "once a visit, key and value"}


def _mla_attn_kernel(
    # scalar prefetch
    table_ref,  # (R, max_pages) SMEM
    descr_ref,  # (M, 4) SMEM, a tile each: page-table row, its first query's position, live queries (0: none), packed offset
    # inputs
    q_ref,  # staged: (T + W, H, lanes) ANY/HBM, the packed queries; else (M, H, lanes) VMEM, tile t's at [t]
    pages_hbm,  # (P, page_size, lanes) ANY/HBM
    # output
    out_ref,  # staged: (T + W, H, value_dim) ANY/HBM; else (M, H, value_dim) VMEM
    # scratch
    buf,  # (2, N, page_size, lanes) VMEM
    sems,  # DMA semaphores (2,): a half of ``buf`` each
    chain_ref,  # (2,) SMEM: is this tile's first visit in flight, and in which half
    s_s,  # (rows, N * page_size) float32: a visit's scores, rows = W * H
    p_s,  # the same in the pool's dtype: its probabilities
    m_s,  # (rows, 1) float32: the running maximum
    l_s,  # and sum
    acc_s,  # (rows, value_dim) float32: the running output
    *staging,  # staged: q_buf (W, H, lanes), o_buf (W, H, value_dim), their DMA semaphores (2,)
    value_dim: int,
    scale: float,
    staged: bool,
):
    """Grid over tiles, in order. A tile is W consecutive queries of one row
    (W = 1: a decode row), the first at position ``start``; query j sees the
    row's keys at positions <= start + j. The tile's H heads x W queries are
    the rows of ONE product against a visit's latents (every head reads the
    same key row), and the probabilities weigh the same buffer's first
    ``value_dim`` lanes: a page is fetched once. A VISIT is up to N consecutive
    pages from a multiple of N, double-buffered in one chain over the whole
    launch (a tile's last visit starts the next live tile's first); only a
    visit that holds a key past ``start`` is masked. Queries past the tile's
    live ones come out as if live and are the caller's to drop."""
    t = pl.program_id(0)
    M = pl.num_programs(0)
    _, N, page_size, lanes = buf.shape
    rows = s_s.shape[0]
    heads = q_ref.shape[1]
    W = rows // heads
    last_page = pages_hbm.shape[0] - 1
    zero = jnp.int32(0)

    @pl.when(t == 0)
    def _():
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        chain_ref[0] = 0
        chain_ref[1] = 0
        if not staged:
            out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def page_dma(par, idx, i):
        return pltpu.make_async_copy(pages_hbm.at[idx], buf.at[par, i], sems.at[par])

    def pages_of(tile):
        return _div(descr_ref[tile, 1] + descr_ref[tile, 2] + (page_size - 1), page_size)

    def fetch(par, tile, p0):
        """Start the DMAs of tile ``tile``'s visit from page ``p0``."""
        row = descr_ref[tile, 0]

        def one(i, c):
            # Clamped: a stale table entry must not take the DMA out of bounds.
            page_dma(par, jnp.minimum(jnp.maximum(table_ref[row, p0 + i], 0), last_page), i).start()
            return c

        jax.lax.fori_loop(zero, jnp.minimum(pages_of(tile) - p0, N), one, 0)

    def arrived(par, n):
        jax.lax.fori_loop(zero, n, lambda i, c: (page_dma(par, 0, i).wait(), c)[1], 0)  # a wait counts bytes: any page's

    n_q = descr_ref[t, 2]

    @pl.when(n_q > 0)
    def _tile():
        start, off = descr_ref[t, 1], descr_ref[t, 3]
        last = pages_of(t)
        nxt = jax.lax.while_loop(lambda i: (i < M) & (descr_ref[jnp.minimum(i, M - 1), 2] == 0),
                                 lambda i: i + 1, t + 1)

        @pl.when(chain_ref[0] == 0)
        def _():
            fetch(0, t, zero)

        if staged:
            q_buf, o_buf, io_sems = staging
            load = pltpu.make_async_copy(q_ref.at[pl.ds(off, W)], q_buf, io_sems.at[0])
            load.start()
            load.wait()
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

        def mask(p0):
            key_pos = p0 * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, N * page_size), 1)
            q_pos = start + _div(jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), heads)
            s_s[...] = jnp.where(key_pos <= q_pos, s_s[...], NEG_INF)

        def visit_body(carry):
            p0, par = carry
            more = p0 + N < last

            @pl.when(more | (nxt < M))
            def _():
                fetch(1 - par, jnp.where(more, t, jnp.minimum(nxt, M - 1)), jnp.where(more, p0 + N, 0))

            arrived(par, jnp.minimum(last - p0, N))
            q = (q_buf[...] if staged else q_ref[t]).reshape(rows, lanes)
            s_s[...] = scale * jax.lax.dot_general(
                q, buf[par].reshape(N * page_size, lanes).astype(q.dtype),
                dimension_numbers=(((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            pl.when((p0 + N) * page_size - 1 > start)(functools.partial(mask, p0))
            s = s_s[...]
            m = m_s[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            probs = jnp.exp(s - m_new)
            l_s[...] = l_s[...] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
            m_s[...] = m_new
            p_s[...] = probs.astype(p_s.dtype)
            value = buf[par, :, :, pl.ds(0, value_dim)].reshape(N * page_size, value_dim)
            acc_s[...] = acc_s[...] * alpha + jnp.dot(p_s[...], value, preferred_element_type=jnp.float32)
            return p0 + N, 1 - par

        _, par = jax.lax.while_loop(lambda c: c[0] < last, visit_body,
                                    (zero, jnp.where(chain_ref[0] == 1, chain_ref[1], 0)))
        out = (acc_s[...] / jnp.maximum(l_s[...], 1e-20)).astype(out_ref.dtype).reshape(W, heads, value_dim)
        if staged:
            o_buf[...] = out
            store = pltpu.make_async_copy(o_buf, out_ref.at[pl.ds(off, W)], io_sems.at[1])
            store.start()
            store.wait()
        else:
            out_ref[t] = out[0]
        chain_ref[0] = (nxt < M).astype(jnp.int32)
        chain_ref[1] = par


def _launch(q, pages, table, descr, *, tile: int, value_dim: int, scale: float, pages_per_visit: int,
            staged: bool, interpret: bool, name: str):
    """One launch of the kernel over ``descr``'s tiles of ``tile`` queries.
    ``q`` (staged: the packed queries with a tile of slack; else a tile's
    query a row), lanes padded to the 128 grid with zeros."""
    M = descr.shape[0]
    _, H, lanes = q.shape
    _, page_size, width = pages.shape
    N, rows = pages_per_visit, tile * H
    out_shape = (q.shape[0], H, value_dim)
    whole = lambda shape: pl.BlockSpec(shape, lambda t, *_: (0,) * len(shape))
    anywhere = pl.BlockSpec(memory_space=pltpu.ANY)
    staging = [pltpu.VMEM((tile, H, lanes), q.dtype), pltpu.VMEM((tile, H, value_dim), q.dtype),
               pltpu.SemaphoreType.DMA((2,))] if staged else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M,),
        in_specs=[anywhere if staged else whole(q.shape), anywhere],
        out_specs=anywhere if staged else whole(out_shape),
        scratch_shapes=[
            pltpu.VMEM((2, N, page_size, lanes), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((rows, N * page_size), jnp.float32),
            pltpu.VMEM((rows, N * page_size), pages.dtype),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, value_dim), jnp.float32),
            *staging,
        ],
    )
    kernel = functools.partial(_mla_attn_kernel, value_dim=value_dim, scale=scale, staged=staged)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=name,
    )(table.astype(jnp.int32), descr.astype(jnp.int32), q, pages)


def _lane_padded(q, pages):
    """The queries in the pool's product dtype, their lanes padded with zeros
    to the pool row's (whose own pad lanes hold zeros)."""
    q = q.astype(jnp.promote_types(q.dtype, pages.dtype))
    return jnp.pad(q, ((0, 0), (0, 0), (0, pages.shape[-1] - q.shape[-1])))


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "interpret", "pages_per_visit"))
def mla_paged_attention_tpu(q, pages, page_table, lengths, *, value_dim: int, scale: float,
                            interpret: bool = False, pages_per_visit: int | None = None):
    """q (B, H, <= width) absorbed queries, one a slot; pages (P, page_size,
    width); lengths (B,) (0: an empty slot, whose row comes out zeros).
    Returns (B, H, value_dim): the softmax-weighted latents."""
    B, H, _ = q.shape
    _, page_size, width = pages.shape
    N = pages_per_visit or mla_walk_plan(H, width, page_size, page_table.shape[1], pages.dtype)["one_query_pages_per_visit"]
    slots = jnp.arange(B, dtype=jnp.int32)
    lengths = lengths.astype(jnp.int32)
    descr = jnp.stack([slots, lengths - 1, (lengths > 0).astype(jnp.int32), slots], axis=1)
    return _launch(_lane_padded(q, pages), pages, page_table, descr, tile=1, value_dim=value_dim, scale=scale,
                   pages_per_visit=N, staged=False, interpret=interpret, name="mla_decode_attn_kernel")


def _chunk_tiles(q_starts, q_lens, kv_lens, tile: int, count: int):
    """The rows' queries as ``count`` tiles of ``tile`` (a static bound; the
    dead ones last), in PACKED order: a tile's tail lies over the positions
    of rows packed after it, whose own tiles are written later. Returns the
    kernel's descriptors (count, 4)."""
    order = jnp.argsort(jnp.where(q_lens > 0, q_starts, jnp.iinfo(jnp.int32).max))
    lens, starts, kv = q_lens[order], q_starts[order], kv_lens[order]
    per_row = (lens + tile - 1) // tile
    ends = jnp.cumsum(per_row)
    m = jnp.arange(count, dtype=jnp.int32)
    i = jnp.minimum(jnp.searchsorted(ends, m, side="right"), q_lens.shape[0] - 1)
    c = m - (ends[i] - per_row[i])
    live = m < ends[-1]
    n_q = jnp.where(live, jnp.minimum(lens[i] - c * tile, tile), 0)
    return jnp.stack([order[i], kv[i] - lens[i] + c * tile, n_q, starts[i] + c * tile], axis=1).astype(jnp.int32)


def _written(T: int, q_starts, lens):
    """(T,) the packed positions some row's ``lens`` queries sit at."""
    t = jnp.arange(T, dtype=jnp.int32)[:, None]
    return jnp.any((t >= q_starts[None, :]) & (t < (q_starts + lens)[None, :]), axis=1)


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "interpret", "pages_per_visit"))
def mla_pair_attention_tpu(q, pages, page_table, q_starts, q_lens, kv_lens, *, value_dim: int, scale: float,
                           interpret: bool = False, pages_per_visit: int | None = None):
    """The rows of at most TWO queries (ISSUE 56: a decoding row's pending token
    and its draft; ONE where the module leaves a refused draft out): q (T, H,
    width) packed, a tile of two a row of the page table, its queries the row's
    last positions. Returns (T, H, value_dim); zeros wherever no such row's
    queries sit."""
    T, H, _ = q.shape
    R, max_pages = page_table.shape
    _, page_size, width = pages.shape
    N = pages_per_visit or mla_walk_plan(H, width, page_size, max_pages, pages.dtype)["pair_pages_per_visit"]
    lens = jnp.where(q_lens <= 2, q_lens, 0).astype(jnp.int32)
    descr = jnp.stack([jnp.arange(R, dtype=jnp.int32), kv_lens.astype(jnp.int32) - lens, lens,
                       jnp.where(lens > 0, q_starts, 0).astype(jnp.int32)], axis=1)
    qp = jnp.pad(_lane_padded(q, pages), ((0, 2), (0, 0), (0, 0)))
    out = _launch(qp, pages, page_table, descr, tile=2, value_dim=value_dim, scale=scale, pages_per_visit=N,
                  staged=True, interpret=interpret, name="mla_pair_attn_kernel")[:T]
    return jnp.where(_written(T, q_starts.astype(jnp.int32), lens)[:, None, None], out, 0)


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "interpret", "pages_per_visit", "row_pages_per_visit",
                                             "pair"))
def mla_ragged_attention_tpu(q, pages, page_table, q_starts, q_lens, kv_lens, *, value_dim: int, scale: float,
                             interpret: bool = False, pages_per_visit: int | None = None,
                             row_pages_per_visit: int | None = None, pair: str | None = None):
    """q (T, H, width) packed absorbed queries; the descriptors of
    ``ragged_paged_attention_tpu``. Returns (T, H, value_dim); zeros where no
    row's query sits. ``pair``: None, the one-query rows of the packed prefix go
    to the decode launch and every other row is cut into the chunk's tiles;
    "mixed", the rows of one or two go to the pair launch and the rest to the
    chunk's tiles; "only", every live row has one or two (a decode chunk's
    round) and the pair launch is the one launch."""
    T, H, _ = q.shape
    R, max_pages = page_table.shape
    _, page_size, width = pages.shape
    plan = mla_walk_plan(H, width, page_size, max_pages, pages.dtype)
    if pair == "only":
        return mla_pair_attention_tpu(q, pages, page_table, q_starts, q_lens, kv_lens, value_dim=value_dim,
                                      scale=scale, interpret=interpret)
    tile = min(plan["wide_tile"], -(-T // 8) * 8)
    n = min(R, T)
    if pair is not None:
        out_1 = mla_pair_attention_tpu(q, pages, page_table, q_starts, q_lens, kv_lens, value_dim=value_dim,
                                       scale=scale, interpret=interpret)
        lens = jnp.where(q_lens <= 2, 0, q_lens).astype(jnp.int32)
    else:
        table_1, lengths_1, to_decode = _one_query_prefix(page_table, q_starts, q_lens, kv_lens, n)
        out_1 = jnp.pad(mla_paged_attention_tpu(q[:n], pages, table_1, lengths_1, value_dim=value_dim, scale=scale,
                                                interpret=interpret, pages_per_visit=row_pages_per_visit),
                        ((0, T - n), (0, 0), (0, 0)))
        lens = jnp.where(to_decode, 0, q_lens).astype(jnp.int32)
    descr = _chunk_tiles(q_starts.astype(jnp.int32), lens, kv_lens.astype(jnp.int32), tile, -(-T // tile) + R)
    qp = jnp.pad(_lane_padded(q, pages), ((0, tile), (0, 0), (0, 0)))  # a tile of slack: a row's last tile may overhang
    out = _launch(qp, pages, page_table, descr, tile=tile, value_dim=value_dim, scale=scale,
                  pages_per_visit=pages_per_visit or plan["wide_pages_per_visit"], staged=True,
                  interpret=interpret, name="mla_chunk_attn_kernel")[:T]
    # What no tile wrote is whatever the buffer held: chosen away, never multiplied.
    theirs = _written(T, q_starts.astype(jnp.int32), lens)
    return jnp.where(theirs[:, None, None], out, 0) + out_1


def _as_one_key_head(q, pages, scale: float):
    """The twins scale by width ** -0.5: fold this attention's scale into q."""
    q = _lane_padded(q, pages)
    return (q.astype(jnp.float32) * (scale * q.shape[-1] ** 0.5)).astype(q.dtype)


def _use_kernel(q, pages) -> tuple[bool, bool]:
    """(the kernel?, interpreted?) by ``paged_dispatch``, the one decision, for
    one key head on one device: no mesh carries the latent pool."""
    platform = device_platform()
    path, _ = paged_dispatch(1, q.shape[1], pages.shape[-1], platform=platform, force=FORCE_PAGED_KERNEL)
    return path == "kernel", platform != "tpu"


def mla_paged_attention(q, pages, page_table, lengths, *, value_dim: int, scale: float):
    """Dispatch of the one-query read: the kernel on a TPU, the twin elsewhere."""
    kernel, interpret = _use_kernel(q, pages)
    if kernel:
        return mla_paged_attention_tpu(q, pages, page_table, lengths, value_dim=value_dim, scale=scale,
                                       interpret=interpret)
    return paged_attention_jax(_as_one_key_head(q, pages, scale), pages, pages, page_table, lengths, 1)[..., :value_dim]


def mla_ragged_attention(q, pages, page_table, q_starts, q_lens, kv_lens, *, value_dim: int, scale: float,
                         pair: str | None = None):
    """Dispatch of the mixed step's read (``pair``: ``mla_ragged_attention_tpu``'s)."""
    kernel, interpret = _use_kernel(q, pages)
    if kernel:
        return mla_ragged_attention_tpu(q, pages, page_table, q_starts, q_lens, kv_lens, value_dim=value_dim,
                                        scale=scale, interpret=interpret, pair=pair)
    return ragged_paged_attention_jax(_as_one_key_head(q, pages, scale), pages, pages, page_table, q_starts, q_lens,
                                      kv_lens, 1)[..., :value_dim]
