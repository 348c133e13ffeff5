"""A step's rows into the flat pools as copies of the runs they lie in.

``write_rows(pools, rows, idx)`` is ``pool.at[idx].set(rows, mode="drop")`` for
each pool of ``pools`` (the keys' and the values', one launch for both), for
indices that are unique where they lie in the pool. On the chip XLA's scatter
of T rows is T read-modify-writes, one after another, of the TILE a row lies in
(16 rows of a bf16 pool: 128 KB for a row of 8 KB), whatever the rows'
neighbours are: ~0.2-0.3 us a row (PERF.md section 5, PR 62). A step's rows are
mostly RUNS: a prompt chunk's rows lie back to back inside a page. The kernel
here reads the indices as scalars, finds the runs from them (a run is rows whose
indices step by exactly 1 inside one tile of the pool; it is proven from the
indices, never assumed from how the host packed the step), and writes each
tile a run covers ONCE: whole where the run fills it, merged with what the pool
holds there where it does not (a run's first and last tile, a decoding row).
The pool is aliased through the call and only the touched tiles move; the
merge is integer work on the rows' bits, so nothing is rounded.

The choice between the kernel and the scatter is ``pool_write_dispatch``'s, by
what the code sees (platform, mesh, the static T and row width); the engine
reports it as ``pool_write_plan`` in ``/debug/status``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from inference_gateway_tpu.utils.platform import on_tpu

LANE = 128
SLOTS = 8            # tiles in flight a pool: a write is waited for when its slot comes round
AHEAD = 3            # how far the reads of partly covered tiles run before the merges (under SLOTS)
# The step's rows stand in VMEM whole (every pool's): above this the scatter is kept.
ROWS_VMEM_BYTES = 24 << 20
# Below this many rows the scatter is as fast (the decode programs' 5-64 rows:
# PERF.md section 5, PR 62's table), so those shapes keep it.
MIN_ROWS = 128


def granule(dtype) -> int:
    """Rows of a tile of the pool: what one copy moves (8 rows of 32 bits)."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def pool_write_dispatch(rows: int, width: int, dtype, pool_rows: int, n_pools: int = 2,
                        platform: str = "tpu", sharded: bool = False) -> tuple[str, str]:
    """(path, reason); path is "runs" (the kernel) or "scatter". A pure function of
    the static shape, the platform and whether the pool is sharded."""
    if platform != "tpu":
        return "scatter", f"platform {platform} is not TPU"
    if sharded:
        return "scatter", "a sharded pool"
    item = jnp.dtype(dtype).itemsize
    if item not in (2, 4):
        return "scatter", f"rows of {item}-byte elements"
    g = granule(dtype)
    if width % LANE or pool_rows % g:
        return "scatter", f"a row of {width} elements or a pool of {pool_rows} rows is off the ({g}, {LANE}) tiling"
    if rows < MIN_ROWS:
        return "scatter", f"{rows} rows: under {MIN_ROWS} the scatter is as fast"
    if n_pools * -(-rows // g) * g * width * item > ROWS_VMEM_BYTES:
        return "scatter", f"{rows} rows of {width * item} bytes do not stand in VMEM"
    return "runs", f"{rows} rows of {width * item} bytes as copies of their runs, tiles of {g} rows"


def sharded_over(mesh) -> bool:
    """Whether pools under ``mesh`` are split over devices (the kernel writes a whole pool)."""
    return mesh is not None and mesh.devices.size > 1


def pool_write_plan(programs: dict, width: int, dtype, pool_rows: int, platform: str, sharded: bool) -> dict:
    """``/debug/status`` ``pool_write``: for each step program (name -> the rows a
    launch of it writes a layer) the path its rows take into the pools, a static
    verdict; ``tile_rows`` is what one copy moves at most."""
    plan = {"tile_rows": granule(dtype)}
    for name, rows in programs.items():
        path, reason = pool_write_dispatch(rows, width, dtype, pool_rows, platform=platform, sharded=sharded)
        plan[name] = {"path": path, "rows": rows, "row_bytes": width * jnp.dtype(dtype).itemsize, "reason": reason}
    return plan


def _kernel(idx_ref, bound_ref, *refs, n_pools: int, G: int):
    """idx_ref (T,) and bound_ref (1,) in SMEM: the rows' indices (a dropped row's
    is the pool's row count or more) and one past the last row that is kept.
    refs: the rows (T, W) a pool and the pools (N, W) in HBM, the pools again as
    results (aliased), then the scratch. T is a multiple of 8 (a copy's tiling)."""
    rows_hbm = refs[:n_pools]
    T = rows_hbm[0].shape[0]
    pools = refs[2 * n_pools:3 * n_pools]
    src, tiles, item_t, item_n, ring, rows_sem, rsem, wsem = refs[3 * n_pools:]
    N, W = pools[0].shape
    Tp = src.shape[1]
    pack = G // 8          # rows a 32-bit word
    bound = bound_ref[0]
    u32 = jnp.uint32

    def tile_copy(kind, p, slot, tile):
        at = pools[p].at[pl.ds(pl.multiple_of(tile * G, G), G)]
        if kind == "read":
            return pltpu.make_async_copy(at, tiles.at[slot, p], rsem.at[slot, p])
        return pltpu.make_async_copy(tiles.at[slot, p], at, wsem.at[slot, p])

    def wait_write(j):
        for p in range(n_pools):
            tile_copy("write", p, j % SLOTS, 0).wait()

    def wait_writes(done, upto):
        """Waits for the writes of items [done, upto)."""
        def one(j, _):
            wait_write(j)
            return 0
        jax.lax.fori_loop(done, upto, one, 0)

    @pl.when(bound > 0)
    def _():
        loads = [pltpu.make_async_copy(rows_hbm[p], src.at[p, pl.ds(0, T)], rows_sem.at[p]) for p in range(n_pools)]
        for load in loads:
            load.start()

        # The items: a run's rows inside one tile. item_t its first row, item_n how many.
        def next_item(c):
            t, m = c
            first = idx_ref[t]
            kept = (first >= 0) & (first < N)
            room = jnp.minimum(G - first % G, bound - t)          # rows left in the tile, and in the step

            def grows(n):
                return (n < room) & (idx_ref[jnp.minimum(t + n, T - 1)] == first + n)
            n = jax.lax.while_loop(grows, lambda n: n + 1, jnp.int32(1))
            n = jnp.where(kept, n, 1)

            @pl.when(kept)
            def _():
                item_t[m] = t
                item_n[m] = n
            return t + n, m + kept.astype(jnp.int32)

        _, M = jax.lax.while_loop(lambda c: c[0] < bound, next_item, (jnp.int32(0), jnp.int32(0)))
        for s in range(SLOTS):
            ring[s] = -1
        for load in loads:      # (the items were found while the step's rows came in)
            load.wait()

        def merged(p, slot, t, off, n, lo, hi, q):
            """The tile of item (t, off, n) of pool p: rows [off, off + n) from the
            step's rows t.., the rest as the tile in ``tiles`` holds them. In 32-bit
            words (``pack`` rows a word), so a row's bits move and nothing else."""
            words = jnp.concatenate([pltpu.bitcast(src[p, pl.ds(lo, G), :], u32),
                                     pltpu.bitcast(src[p, pl.ds(hi, G), :], u32)], axis=0)   # (2G / pack, W)
            R = 2 * G // pack
            k = q // pack
            near = pltpu.roll(words, (R - k) % R, 0)[:G // pack]
            j = jax.lax.broadcasted_iota(jnp.int32, (G // pack, W), 0)
            if pack == 1:
                new = near
                mask = jnp.where((j >= off) & (j < off + n), u32(0xFFFFFFFF), u32(0))
            else:
                far = pltpu.roll(words, (R - k - 1) % R, 0)[:G // pack]
                new = jnp.where(q % 2 == 1, (near >> 16) | (far << 16), near)
                inside = lambda r: (r >= off) & (r < off + n)
                mask = (jnp.where(inside(2 * j), u32(0xFFFF), u32(0))
                        | jnp.where(inside(2 * j + 1), u32(0xFFFF0000), u32(0)))
            old = pltpu.bitcast(tiles[slot, p], u32)
            return pltpu.bitcast((new & mask) | (old & ~mask), tiles.dtype)

        def read_ahead(k, c):
            """One try to take item ``ahead`` its slot: the write that held the slot is
            waited for and, where the item's run does not fill its tile, the tile is
            read. A tile that one of the SLOTS - 1 items before it also writes is read
            only when those are written: the try waits until the item is the next to
            merge (k). ``done``: the writes of items under it have been waited for."""
            ahead, done = c
            at = jnp.where(ahead < M, ahead, 0)      # (past the last item: nothing is taken, and no item's row is read)
            slot = ahead % SLOTS
            tile = idx_ref[item_t[at]] // G
            clash = jnp.bool_(False)
            for s in range(SLOTS):
                clash = clash | ((ring[s] == tile) & (s != slot))
            go = (ahead < M) & (ahead <= k + AHEAD) & (jnp.logical_not(clash) | (ahead == k))

            @pl.when(go & clash)
            def _():
                wait_writes(done, ahead)

            @pl.when(go & jnp.logical_not(clash) & (ahead - SLOTS >= done))
            def _():
                wait_write(ahead - SLOTS)

            @pl.when(go)
            def _():
                ring[slot] = tile

            @pl.when(go & (item_n[at] < G))
            def _():
                for p in range(n_pools):
                    tile_copy("read", p, slot, tile).start()
            free = jnp.maximum(done, jnp.where(clash, ahead, ahead - SLOTS + 1))
            return ahead + go.astype(jnp.int32), jnp.where(go, free, done)

        def step(i, c):
            """Round i: one try of the reads for item ``ahead`` (they lead by AHEAD items, so
            the first AHEAD rounds only read), then item k = i - AHEAD is merged and written."""
            ahead, done = c
            live = i >= AHEAD
            k = jnp.maximum(i - AHEAD, 0)
            ahead, done = read_ahead(k, (ahead, done))
            slot = k % SLOTS
            t, n = item_t[k], item_n[k]
            first = idx_ref[t]
            tile, off = first // G, first % G
            s0 = t - off                                   # the step's row that lands on the tile's row 0
            a = (s0 + G) // G * G - G                      # floor to the tiling: -G where s0 < 0
            lo = pl.multiple_of(jnp.maximum(a, 0), G)      # clamped windows hold no row that is kept
            hi = pl.multiple_of(jnp.minimum(a + G, Tp - G), G)

            @pl.when(live & (n < G))
            def _():
                for p in range(n_pools):
                    tile_copy("read", p, slot, tile).wait()

            @pl.when(live)
            def _():
                for p in range(n_pools):
                    tiles[slot, p] = merged(p, slot, t, off, n, lo, hi, s0 - a)
                for p in range(n_pools):
                    tile_copy("write", p, slot, tile).start()
            return ahead, done

        _, done = jax.lax.fori_loop(0, M + AHEAD, step, (jnp.int32(0), jnp.int32(0)))
        wait_writes(done, M)


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_rows_tpu(pools, rows, idx, *, interpret: bool = False):
    """``write_rows`` by the kernel ``pool_write_kernel``: the pools in place."""
    n_pools = len(pools)
    N, W = pools[0].shape
    T = rows[0].shape[0]
    dtype = pools[0].dtype
    G = granule(dtype)
    assert N % G == 0 and jnp.dtype(dtype).itemsize in (2, 4), (N, W, dtype)   # (the lanes: the dispatch's)
    assert all(p.shape == (N, W) and p.dtype == dtype for p in pools) and all(r.shape == (T, W) for r in rows)
    if T % 8:   # a copy into VMEM moves whole tiles of 8 rows
        rows = tuple(jnp.pad(r, ((0, -T % 8), (0, 0))) for r in rows)
        idx = jnp.pad(idx, (0, -T % 8), constant_values=N)
        T = rows[0].shape[0]
    Tp = max(-(-T // G) * G, 2 * G)
    idx = idx.astype(jnp.int32)
    idx = jnp.where(idx < 0, idx + N, idx)   # as ``.at[]`` reads a negative index
    kept = (idx >= 0) & (idx < N)
    bound = jnp.max(jnp.where(kept, jnp.arange(T, dtype=jnp.int32) + 1, 0)).reshape(1)
    kernel = functools.partial(_kernel, n_pools=n_pools, G=G)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[hbm] * (2 * n_pools),
            out_specs=[hbm] * n_pools,
            scratch_shapes=[
                pltpu.VMEM((n_pools, Tp, W), dtype),          # the step's rows
                pltpu.VMEM((SLOTS, n_pools, G, W), dtype),    # tiles in flight
                pltpu.SMEM((T,), jnp.int32),                  # an item's first row
                pltpu.SMEM((T,), jnp.int32),                  # and its rows
                pltpu.SMEM((SLOTS,), jnp.int32),              # the tile a slot holds
                pltpu.SemaphoreType.DMA((n_pools,)),
                pltpu.SemaphoreType.DMA((SLOTS, n_pools)),
                pltpu.SemaphoreType.DMA((SLOTS, n_pools)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={2 + n_pools + p: p for p in range(n_pools)},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="pool_write_kernel",
    )(idx, bound, *(r.astype(dtype) for r in rows), *pools)
    return tuple(out)


def write_rows(pools, rows, idx, *, mesh=None):
    """Each ``pool.at[idx].set(rows, mode="drop")`` of ``pools`` (tuples of the
    same length: the keys' and the values' pools (N, W), and their rows, ``idx``'s
    shape and then W), for ``idx`` unique where it lies in the pool; a row whose
    index is the pool's row count or more is dropped. The run-copy kernel where
    ``pool_write_dispatch`` says so, the scatter elsewhere (off the chip, pools
    sharded over ``mesh``, the shapes where it is as fast)."""
    N, W = pools[0].shape
    path, _ = pool_write_dispatch(idx.size, W, pools[0].dtype, N, len(pools), platform="tpu" if on_tpu() else "cpu",
                                  sharded=sharded_over(mesh))
    if path == "runs":
        return write_rows_tpu(tuple(pools), tuple(r.reshape(idx.size, W) for r in rows), idx.reshape(-1),
                              interpret=not on_tpu())
    return tuple(pool.at[idx].set(r, mode="drop") for pool, r in zip(pools, rows))
