"""Mamba-2's state-space mixer (state-space duality, arXiv:2405.21060) for
serving: the scan of a model whose state is a MATRIX a head and whose decay is
ONE scalar a head a token (ISSUE 50). Beside ``ops/ssm.py`` (Mamba-1: a decay a
channel a state column, one trip a token), whose convolution this mixer shares.

    Delta_t = softplus(dt_t + dt_bias);  a_t = Delta_t A          (A = -exp(A_log) < 0, a head)
    S_t = exp(a_t) S_{t-1} + Delta_t x_t B_t^T;   y_t = S_t C_t + D x_t

a head: x_t (P,), B_t and C_t (N,) shared by every head (one group), S (P, N)
float32. A slot's state is ``(heads, P, N)``; the store of a layer stack is
``(state layers, slots, heads, P, N)``.

Two forms, as ``ops/ssm.py`` has them:

- ``update_store``: every row is one slot advancing ONE token, batched over
  the slots. The decode chunk's rows and a mixed step's one-token rows take
  it. On a TPU it is the Pallas kernel ``ssm_state_update_kernel`` (ISSUE 63):
  ONE program that walks the rows that advance (``walk_rows``, which
  ``ops/kda.py``'s update shares), a visit moving about a megabyte of ONE
  row's states (``visit_heads``: by the state's shape) into VMEM and back in
  place by DMA while the next comes in and the one before goes back; a slot
  that does not advance is never visited, so the store's traffic is the
  advancing rows' states, once in and once out, and so is the launch's time.
  Elsewhere it is ``state_update``, the definition in plain ``jax.numpy`` over
  the layer's whole entry.
- ``ssd_chunk``: the rows of a mixed step that hold MORE than one token, in
  the blocked form. The scalar decay lets a block of Q tokens be matrix
  products: with s_t the running sum of a inside the block,

      Y = ((C B^T) * L)(Delta * X) + exp(s) * (C S_in^T)      L_ts = exp(s_t - s_s), s <= t
      S_out = exp(s_Q) S_in + sum_t exp(s_Q - s_t) Delta_t x_t B_t^T

  (differences of s alone, all <= 0: nothing overflows). In exact arithmetic
  that IS the recurrence whatever Q. A row's first block starts from its
  slot's stored state, from zero where the row starts at position 0 (the
  store's rule: ops/ssm.py), hands S_out to its next block in VMEM and its
  last block's S_out goes back to the slot; no prompt token goes through a
  loop of one trip a token.

**How the blocks lie.** The packed token axis is cut into blocks of Q from 0,
whatever the rows are. A row of more than one token visits every packed block
it touches, in order (``block_plan``); inside a visit the block's tokens that
belong to ANOTHER row count as identity steps (Delta = 0: no decay, no input)
and their outputs are not written. So a block's operands are plain blocked
reads, a ragged row costs at most one visit more than ceil(len / Q), and rows
that share a packed block write their own tokens of the one output block,
which stays in VMEM between their visits (the plan orders rows by their
packed start).

On a TPU ``ssd_chunk`` is the Pallas kernel ``ssd_chunk_kernel``: a grid of
(head blocks, the plan's LIVE visits: the visit axis is a dynamic grid dimension,
``live_visits``, so a visit past the last is no grid step, ISSUE 65), the head
block's states in a VMEM scratch across a row's visits, the store read and written in place by DMA at (layer ordinal, slot).
Tokens lie in the LANES (operands transposed outside, 17 MB a layer at the
published sizes), so a head's P rows are a sublane slice and every product is
a plain (M, K) x (K, N); the two products whose second operand every head
shares (the state against C, the weighted inputs against B) run over the whole
head block at once. The products' OPERANDS are rounded to the model's
activation type (``mm``: float32 at the highest precision, several passes of
the matrix unit; bfloat16 in a bfloat16 engine, as every other product of it
rounds its operands); the running sums, the decays, the accumulation and the
state are float32, so a rounding does not compound from block to block.
Elsewhere ``ssd_chunk`` is the same plan walked by ``lax.fori_loop`` in plain
``jax.numpy`` (the twin; tests hold the interpreted kernel to it and both to
the token recurrence).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from inference_gateway_tpu.utils.platform import on_tpu

HEAD_BLOCK = 8   # heads a grid step of the chunk kernel holds: their states are 8 x P x N float32 of VMEM (256 KB at 64 x 128)
META = 8         # int32 words a visit: packed block, slot, lo, hi, first, last, fresh, init
HIGHEST = jax.lax.Precision.HIGHEST


def state_update(entry, dt, x, B, C, A, D, valid, fresh=None):
    """One token a slot. entry (S, Hh, P, N) float32, the layer's states; dt
    (S, Hh) after softplus; x (S, Hh, P); B, C (S, N); A, D (Hh,); valid (S,)
    rows that advance (the others keep their state); fresh (S,) rows whose
    token is at position 0 (they start from zero). Returns y (S, Hh, P)
    float32 and the new entry. Plain ``jax.numpy``: the definition, and what
    runs off the TPU."""
    with jax.named_scope("ssm.scan"):
        prev = entry if fresh is None else jnp.where(fresh[:, None, None, None], 0.0, entry)
        decay = jnp.exp(dt * A[None, :])
        new = decay[:, :, None, None] * prev + (dt[:, :, None] * x)[..., None] * B[:, None, None, :]
        y = jnp.einsum("shpn,sn->shp", new, C, precision=HIGHEST) + D[None, :, None] * x
        return y, jnp.where(valid[:, None, None, None], new, entry)


VISIT_BYTES = 1 << 20   # the states one visit of a one-token update moves, each way: about a megabyte
RING = 3                # visits in VMEM at once: one coming in, one being advanced, one going back
TILE = 128              # rows of a visit advanced at a time: a square with the state's 128 lanes


def visit_heads(Hh: int, R: int, N: int) -> int:
    """Heads a visit of a one-token update holds: the most that divide ``Hh``
    whose states (R x N float32 a head) are VISIT_BYTES at most; one head where
    a head's own state is more."""
    return max([h for h in range(1, Hh + 1) if Hh % h == 0 and h * R * N * 4 <= VISIT_BYTES], default=1)


def visit_plan(Hh: int, R: int, N: int, kernel: str, platform: str | None, off_the_tiles: str | None) -> dict:
    """A one-token update's plan (``update_plan`` here and in ``ops/kda.py``):
    the heads and bytes a visit moves each way, and ``path``: ``kernel`` on a
    TPU (``platform`` None: this process's) unless the shape is
    ``off_the_tiles``, else ``state_update``; ``reason`` says why."""
    hb = visit_heads(Hh, R, N)
    plan = {"path": "state_update", "visit_heads": hb, "visit_bytes": hb * R * N * 4, "visits_a_row": Hh // hb}
    platform = platform or ("tpu" if on_tpu() else "cpu")
    if platform != "tpu":
        return {**plan, "reason": f"platform {platform} is not TPU"}
    if off_the_tiles:
        return {**plan, "reason": off_the_tiles}
    return {**plan, "path": kernel,
            "reason": f"{hb} of {Hh} heads a visit by the state's shape; the launch walks the rows that advance"}


def _visit_tile(hb: int, P: int) -> int:
    """Rows of a visit advanced at a time: TILE where it divides the visit, else the visit whole."""
    return TILE if (hb * P) % TILE == 0 else hb * P


def update_plan(shape, platform: str | None = None) -> dict:
    """How ``update_store`` advances a store of ``shape`` (state layers, slots,
    Hh, P, N): ``path`` is the kernel's name or ``state_update`` with the
    ``reason``, a function of the static shape and the platform alone; and the
    heads and bytes a visit moves each way. ``/debug/status`` reports it."""
    _, _, Hh, P, N = shape
    hb, off = visit_heads(Hh, P, N), None
    if N != 128 or _visit_tile(hb, P) != TILE:
        off = f"a state of {N} lanes, or a visit of {hb * P} rows, is off the ({TILE}, 128) squares"
    elif TILE % P and P % TILE:
        off = f"a head of {P} rows neither divides {TILE} rows nor is divided by them"
    return visit_plan(Hh, P, N, "ssm_state_update_kernel", platform, off)


def advancing_rows(valid):
    """The slots that advance, in slot order, in front of the others, and how
    many they are: what a one-token kernel walks (int32 (S,), (1,))."""
    order = jnp.argsort(jnp.logical_not(valid), stable=True)
    return order.astype(jnp.int32), jnp.sum(valid).astype(jnp.int32).reshape(1)


def walk_rows(n_ref, rows_ref, ord_ref, store_in, store_out, ring, sem, *, visits: int, rows: int, advance):
    """The loop the one-token kernels share (``ops/kda.py``'s too). Row
    ``rows_ref[i]``, i < ``n_ref[0]``, of entry ``ord_ref[0]`` of the store (HBM:
    state layers, slots, heads x a head's rows, lanes) is taken in ``visits``
    pieces of ``rows`` rows: a piece comes into a place of ``ring`` by DMA,
    ``advance(slot, piece, ref)`` rewrites it there, and it goes back, while the
    next piece comes in and the one before goes back. A slot that does not
    advance is never named: the launch's time follows the rows that do."""
    total = n_ref[0] * visits

    div, rem = jax.lax.div, jax.lax.rem        # (of counts: one traced equation each where // and % are a dozen)

    def move(v, back: bool):
        slot, place = rows_ref[div(v, visits)], rem(v, RING)
        at = lambda ref: ref.at[ord_ref[0], slot, pl.ds(pl.multiple_of(rem(v, visits) * rows, 8), rows)]
        if back:
            return pltpu.make_async_copy(ring.at[place], at(store_out), sem.at[1, place])
        return pltpu.make_async_copy(at(store_in), ring.at[place], sem.at[0, place])

    @pl.when(total > 0)
    def _():
        move(0, False).start()

    def one(v, _):
        @pl.when(v >= RING - 1)
        def _():
            move(v - (RING - 1), True).wait()      # the place the next piece takes is free again

        @pl.when(v + 1 < total)
        def _():
            move(v + 1, False).start()

        move(v, False).wait()
        advance(rows_ref[div(v, visits)], rem(v, visits), ring.at[rem(v, RING)])
        move(v, True).start()
        return 0

    jax.lax.fori_loop(0, total, one, 0)

    def drain(v, _):
        move(v, True).wait()
        return 0

    jax.lax.fori_loop(jnp.maximum(total - (RING - 1), 0), total, drain, 0)


def staged(sources, places, sem):
    """The slots' row operands from HBM into VMEM, all in flight together (the
    kernels' own copies: an operand XLA has to hold in VMEM for a kernel counts
    against the program's, and Kimi's decode program then does not fit)."""
    loads = [pltpu.make_async_copy(src, dst, sem.at[i]) for i, (src, dst) in enumerate(zip(sources, places))]
    for load in loads:
        load.start()
    for load in loads:
        load.wait()


def handed_back(place, result, sem):
    """The slots' results from VMEM to HBM, once the walk is over."""
    back = pltpu.make_async_copy(place, result, sem)
    back.start()
    back.wait()


def _state_update_kernel(n_ref, rows_ref, ord_ref, fresh_ref, decay_ref, x_hbm, b_hbm, c_hbm, store_in, y_hbm, store_out,
                         ring, sem, x_ref, b_ref, c_ref, y_ref, row_sem, *, hb: int, P: int, heads: int, tile: int):
    tiles = hb * P // tile                     # a visit's tiles; x and y lie (slots, a row's tiles, tile)
    y_ref[...] = jnp.zeros_like(y_ref)
    staged((x_hbm, b_hbm, c_hbm), (x_ref, b_ref, c_ref), row_sem)

    def advance(slot, piece, ref):
        fresh = fresh_ref[slot] == 1
        b, c = b_ref[pl.ds(slot, 1), :], c_ref[pl.ds(slot, 1), :]                          # (1, N)

        def one(t, _):
            at = piece * tiles + t                                                         # the tile among the row's
            rows = pl.ds(pl.multiple_of(t * tile, tile), tile)
            state = ref[rows, :]                                                           # (tile, N)
            prev = jnp.where(fresh, jnp.zeros_like(state), state)
            if P <= tile:
                first = slot * heads + at * (tile // P)
                decay = jnp.concatenate([jnp.full((P, ref.shape[1]), decay_ref[first + i], jnp.float32) for i in range(tile // P)])
            else:
                decay = decay_ref[slot * heads + jax.lax.div(at, P // tile)]
            # Delta x of the tile's rows lies in the lanes: ONE turn beside the state's rows.
            x_col = jnp.broadcast_to(x_ref[slot, pl.ds(at, 1), :], (ref.shape[1], tile)).T  # (tile, N)
            new = decay * prev + x_col * b
            ref[rows, :] = new
            y_ref[slot, pl.ds(at, 1), :] = jnp.sum((new * c).T, axis=0, keepdims=True)     # the lane sums, as a row
            return 0

        jax.lax.fori_loop(0, tiles, one, 0, unroll=True)   # (the tiles' turns wait on nothing of one another's)

    walk_rows(n_ref, rows_ref, ord_ref, store_in, store_out, ring, sem, visits=heads // hb, rows=hb * P, advance=advance)
    handed_back(y_ref, y_hbm, row_sem.at[3])


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_update_tpu(store, ordinal, dt, x, B, C, A, D, valid, fresh, *, interpret: bool = False):
    """``state_update`` on the whole store by the Pallas kernel
    ``ssm_state_update_kernel``: one program that walks the rows that advance
    (``walk_rows``), a visit moving ``visit_heads`` heads' states of ONE row in
    and back out by DMA, in place; a slot that does not advance costs nothing
    and keeps its bits. The store's traffic is the advancing rows' states, once
    in and once out; the slots' row operands (Delta x, B, C) and y stand in VMEM
    whole, brought and handed back by the kernel's own copies. Returns y (S, Hh,
    P), zero at the rows that do not advance, and the store."""
    L, S, Hh, P, N = store.shape
    hb = visit_heads(Hh, P, N)
    tile = _visit_tile(hb, P)
    if (tile % P and P % tile) or (hb * P) % 8:
        raise ValueError(f"a head of {P} rows does not tile a visit of {hb * P} rows by {tile}")
    rows = store.reshape(L, S, Hh * P, N)
    order, n = advancing_rows(valid)
    decay = jnp.exp(dt * A[None, :]).reshape(-1).astype(jnp.float32)            # (S * Hh,), read as scalars
    xdt = (dt[:, :, None] * x).reshape(S, Hh * P // tile, tile).astype(jnp.float32)
    smem, hbm = (pl.BlockSpec(memory_space=m) for m in (pltpu.SMEM, pltpu.ANY))
    held = lambda *shape: pltpu.VMEM(shape, jnp.float32)
    y, rows = pl.pallas_call(
        functools.partial(_state_update_kernel, hb=hb, P=P, heads=Hh, tile=tile),
        in_specs=[smem] * 5 + [hbm] * 4,
        out_specs=[hbm, hbm],
        scratch_shapes=[held(RING, hb * P, N), pltpu.SemaphoreType.DMA((2, RING)),
                        held(*xdt.shape), held(S, N), held(S, N), held(*xdt.shape), pltpu.SemaphoreType.DMA((4,))],
        out_shape=[jax.ShapeDtypeStruct(xdt.shape, jnp.float32), jax.ShapeDtypeStruct(rows.shape, rows.dtype)],
        input_output_aliases={8: 1},   # the store
        interpret=interpret,
        name="ssm_state_update_kernel",
    )(n, order, jnp.reshape(ordinal, (1,)).astype(jnp.int32), fresh.astype(jnp.int32), decay,
      xdt, B.astype(jnp.float32), C.astype(jnp.float32), rows)
    return y.reshape(S, Hh, P) + D[None, :, None] * x, rows.reshape(store.shape)


def update_store(store, ordinal, dt, x, B, C, A, D, valid, fresh=None, kernel: bool | None = None):
    """``state_update`` on entry ``ordinal`` of the store (state layers, slots,
    Hh, P, N). Returns (y (S, Hh, P), defined at the valid rows; the store).
    ``kernel`` None: what ``update_plan`` says of the store's shape and the
    platform (the Pallas kernel on a TPU where the state's columns fill the 128
    lanes, plain ``jax.numpy`` elsewhere); tests force the kernel, interpreted
    off the TPU."""
    if kernel is None:
        kernel = update_plan(store.shape)["path"] != "state_update"
    if kernel:
        fresh = jnp.zeros_like(valid) if fresh is None else fresh
        with jax.named_scope("ssm.scan"):
            return state_update_tpu(store, ordinal, dt, x, B, C, A, D, valid, fresh, interpret=not on_tpu())
    entry = jax.lax.dynamic_index_in_dim(store, ordinal, 0, keepdims=False)
    y, entry = state_update(entry, dt, x, B, C, A, D, valid, fresh)
    return y, jax.lax.dynamic_update_index_in_dim(store, entry, ordinal, 0)


def max_visits(T: int, rows: int, Q: int) -> int:
    """An upper bound of ``block_plan``'s visits: every packed block once, and
    once more for every further row (a row's first visit may share its block
    with the row before)."""
    return -(-T // Q) + min(rows, T // 2) - 1 if rows > 1 else -(-T // Q)


def block_plan(q_starts, q_lens, kv_lens, T: int, Q: int):
    """The chunk kernel's visits for a mixed step's descriptors (row r = slot
    r). Returns int32 ``meta`` (visits, META): for each visit the packed block,
    the slot, the token range [lo, hi) of the block that is the row's (hi =
    lo: a visit past the last, nothing to do), whether it is the row's first
    (load the state; ``fresh``: start from zero) and last (store it), and
    ``init``: the first visit of that packed block (the output block is
    zeroed). And ``blocks``, how many visits are live (a float, for the
    step's counters)."""
    R = q_starts.shape[0]
    chunk = q_lens > 1
    order = jnp.argsort(jnp.where(chunk, q_starts, T + jnp.arange(R)))       # chunk rows first, by packed start
    start, length, is_chunk = q_starts[order], q_lens[order], chunk[order]
    first_blk = start // Q
    n = jnp.where(is_chunk, (start + length - 1) // Q - first_blk + 1, 0)
    ends = jnp.cumsum(n)
    V = max_visits(T, R, Q)
    j = jnp.arange(V)
    r = jnp.minimum(jnp.searchsorted(ends, j, side="right"), R - 1)
    live = j < ends[-1]
    k = j - (ends[r] - n[r])
    block = jnp.where(live, first_blk[r] + k, 0)
    lo = jnp.clip(start[r] - block * Q, 0, Q)
    hi = jnp.where(live, jnp.clip(start[r] + length[r] - block * Q, 0, Q), lo)
    # A dead visit stays on the last live visit's block: no block is fetched for it.
    last_block = jnp.max(jnp.where(live, block, 0))
    block = jnp.where(live, block, last_block)
    init = jnp.concatenate([jnp.ones((1,), bool), block[1:] != block[:-1]]) & (live | (j == 0))
    slot = order[r]
    fresh = live & ((kv_lens - q_lens)[slot] == 0)
    meta = jnp.stack([block, slot, lo, hi, live & (k == 0), live & (k == n[r] - 1), fresh, init], axis=1)
    return meta.astype(jnp.int32), jnp.sum(n).astype(jnp.float32)


def live_visits(meta):
    """Where the chunk kernels' visit axis ends (ISSUE 65): the plan's live
    visits, int32, at least 1 (visit 0 stays: it carries ``init``, which zeroes
    the first output block). ``block_plan`` puts the live visits first, so the
    grid's visit dimension is this many steps and a visit past the last is no
    grid step at all; ``meta`` keeps its static length ``max_visits``."""
    return jnp.maximum(jnp.sum(meta[:, 3] > meta[:, 2]), 1).astype(jnp.int32)


def _padded(T: int, Q: int, *arrays):
    """The packed tokens' operands in float32, zeros up to a whole number of blocks."""
    Tp = -(-T // Q) * Q
    return Tp, [jnp.pad(a.astype(jnp.float32), ((0, Tp - T),) + ((0, 0),) * (a.ndim - 1)) for a in arrays]


def _visit_operands(meta, dt, A, Q: int):
    """What a visit needs beside the packed blocks: the running sum of a =
    Delta A over the block with foreign tokens at 0, a head a row (visits, Hh,
    Q), float32."""
    block, lo, hi = meta[:, 0], meta[:, 2], meta[:, 3]
    lane = jnp.arange(Q)[None, :]
    mine = (lane >= lo[:, None]) & (lane < hi[:, None])                       # (V, Q)
    tok = block[:, None] * Q + lane                                            # (V, Q) packed positions
    a = jnp.where(mine[:, :, None], dt[tok] * A[None, None, :], 0.0)           # (V, Q, Hh)
    return jnp.cumsum(a, axis=1).transpose(0, 2, 1)                            # (V, Hh, Q)


def _ssd_chunk_kernel(ord_ref, meta_ref, xT_ref, dt_ref, cr_ref, cc_ref, b_ref, ct_ref, store_in,
                      yT_ref, store_out, s_ref, sem, *, hb: int, Q: int, P: int, mm):
    h, j = pl.program_id(0), pl.program_id(1)
    word = lambda k: meta_ref[j * META + k]
    slot, lo, hi = word(1), word(2), word(3)
    first, last, fresh, init = word(4) == 1, word(5) == 1, word(6) == 1, word(7) == 1
    live = hi > lo
    mine_at = lambda ref: ref.at[ord_ref[0], slot, pl.ds(h * hb * P, hb * P)]
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=HIGHEST if mm == jnp.float32 else None)

    @pl.when(init)
    def _():
        yT_ref[...] = jnp.zeros_like(yT_ref)

    @pl.when(live & first & jnp.logical_not(fresh))
    def _():
        load = pltpu.make_async_copy(mine_at(store_in), s_ref, sem.at[0])
        load.start()
        load.wait()

    @pl.when(live & first & fresh)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(live)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
        mine = (lane >= lo) & (lane < hi)                                      # (1, Q)
        dtm = jnp.where(mine, dt_ref[...], 0.0)                                # (hb, Q)
        cr = cr_ref[...]                                                       # (hb, Q): s_t in the lanes
        b, ct = b_ref[...].astype(mm), ct_ref[...].astype(mm)                  # (Q, N), (N, Q)
        gt = dot(b, ct)                                                        # (Q, Q): [s, t] = B_s . C_t
        lower = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
                 <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))            # s <= t
        # A head's row of (hb, W) spread over its P rows of the head block: (hb * P, W).
        by_head = lambda v, W: jnp.concatenate([jnp.broadcast_to(v[i:i + 1, :], (P, W)) for i in range(hb)], axis=0)
        total = jnp.min(cr, axis=1, keepdims=True)                             # s at the block's end (s never rises)
        x, state = xT_ref[...], s_ref[...]                                     # (hb * P, Q), (hb * P, N)
        # The two products whose second operand every head shares, all heads at once.
        from_state = dot(state.astype(mm), ct) * by_head(jnp.exp(cr), Q)
        carry = (x * by_head(jnp.exp(total - cr) * dtm, Q)).astype(mm)
        s_ref[...] = by_head(jnp.exp(total), state.shape[1]) * state + dot(carry, b)
        xd = (x * by_head(dtm, Q)).astype(mm)
        for i in range(hb):
            rows = slice(i * P, (i + 1) * P)
            s_t, s_s = cr[i:i + 1, :], cc_ref[:, i:i + 1]                       # (1, Q), (Q, 1)
            decay = jnp.where(lower, jnp.exp(jnp.minimum(s_t - s_s, 0.0)), 0.0)
            y = dot(xd[rows], (gt * decay).astype(mm)) + from_state[rows]
            yT_ref[rows, :] = jnp.where(mine, y, yT_ref[rows, :])

    @pl.when(live & last)
    def _():
        save = pltpu.make_async_copy(s_ref, mine_at(store_out), sem.at[1])
        save.start()
        save.wait()


@functools.partial(jax.jit, static_argnames=("Q", "mm", "interpret"))
def ssd_chunk_tpu(store, ordinal, x, dt, B, C, A, meta, *, Q: int, mm=jnp.float32, interpret: bool = False):
    """``ssd_chunk`` by the Pallas kernel. The store is updated in place."""
    T, Hh, P = x.shape
    N = B.shape[1]
    V = meta.shape[0]
    hb = HEAD_BLOCK if Hh % HEAD_BLOCK == 0 else Hh
    Tp, (x, dt, B, C) = _padded(T, Q, x, dt, B, C)
    cum = _visit_operands(meta, dt, A, Q)                                      # (V, Hh, Q)
    cum_cols = cum.reshape(V, Hh // hb, hb, Q).transpose(0, 1, 3, 2)           # (V, Hh / hb, Q, hb)
    at = lambda k: (lambda h, j, o, m: k(h, j, m))
    block_of = lambda j, m: m[j * META]
    kernel = functools.partial(_ssd_chunk_kernel, hb=hb, Q=Q, P=P, mm=mm)
    rows = store.reshape(store.shape[:2] + (Hh * P, N))                        # a head's P rows under one another
    yT, rows = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Hh // hb, live_visits(meta)),
            in_specs=[
                pl.BlockSpec((hb * P, Q), at(lambda h, j, m: (h, block_of(j, m)))),           # x^T
                pl.BlockSpec((hb, Q), at(lambda h, j, m: (h, block_of(j, m)))),               # Delta^T
                pl.BlockSpec((None, hb, Q), at(lambda h, j, m: (j, h, 0))),                   # s, in the lanes
                pl.BlockSpec((None, None, Q, hb), at(lambda h, j, m: (j, h, 0, 0))),          # s, in the sublanes
                pl.BlockSpec((Q, N), at(lambda h, j, m: (block_of(j, m), 0))),                # B
                pl.BlockSpec((N, Q), at(lambda h, j, m: (0, block_of(j, m)))),                # C^T
                pl.BlockSpec(memory_space=pltpu.ANY),                                         # the store
            ],
            out_specs=[pl.BlockSpec((hb * P, Q), at(lambda h, j, m: (h, block_of(j, m)))),
                       pl.BlockSpec(memory_space=pltpu.ANY)],
            scratch_shapes=[pltpu.VMEM((hb * P, N), jnp.float32), pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((Hh * P, Tp), jnp.float32), jax.ShapeDtypeStruct(rows.shape, rows.dtype)],
        input_output_aliases={8: 1},   # the store: operand 8 counting the two scalar operands
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ssd_chunk_kernel",
    )(jnp.reshape(ordinal, (1,)).astype(jnp.int32), meta.reshape(-1),
      x.reshape(Tp, Hh * P).T, dt.T, cum, cum_cols, B, C.T, rows)
    return yT.T[:T].reshape(T, Hh, P), rows.reshape(store.shape)


def ssd_chunk_twin(store, ordinal, x, dt, B, C, A, meta, *, Q: int, mm=jnp.float32):
    """``ssd_chunk`` in plain ``jax.numpy``: the same visits, one after
    another, every head at once. Operands are ROUNDED to ``mm`` and multiplied
    in float32 (the numbers of a product of ``mm`` operands accumulated in
    float32; the CPU has no such product of bfloat16)."""
    es = functools.partial(jnp.einsum, precision=HIGHEST)
    rounded = lambda a: a.astype(mm).astype(jnp.float32)
    T, Hh, P = x.shape
    Tp, (x, dt, B, C) = _padded(T, Q, x, dt, B, C)
    cum = _visit_operands(meta, dt, A, Q)                                      # (V, Hh, Q)
    entry = jax.lax.dynamic_index_in_dim(store, ordinal, 0, keepdims=False)    # (S, Hh, P, N)
    lower = jnp.arange(Q)[:, None] <= jnp.arange(Q)[None, :]                   # [s, t]

    def visit(j, carry):
        entry, state, y = carry
        block, slot, lo, hi, first, last, fresh, _ = (meta[j, k] for k in range(META))
        live = hi > lo
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, block * Q, Q, 0)
        xb, db, bb, cb = take(x), take(dt), take(B), take(C)                   # (Q, Hh, P), (Q, Hh), (Q, N), (Q, N)
        mine = (jnp.arange(Q) >= lo) & (jnp.arange(Q) < hi)
        dtm = jnp.where(mine[:, None], db, 0.0)
        s = cum[j]                                                             # (Hh, Q)
        state = jnp.where((first == 1) & (fresh == 0), entry[slot], jnp.where(first == 1, 0.0, state))
        decay = jnp.where(lower[None], jnp.exp(jnp.minimum(s[:, None, :] - s[:, :, None], 0.0)), 0.0)   # (Hh, s, t)
        bm, cm = rounded(bb), rounded(cb)
        gt = es("sn,tn->st", bm, cm)
        yb = es("shp,hst->thp", rounded(xb * dtm[:, :, None]), rounded(gt[None] * decay))
        yb = yb + es("hpn,tn->thp", rounded(state), cm) * jnp.exp(s).T[:, :, None]
        total = s[:, -1]
        weight = jnp.exp(total[:, None] - s) * dtm.T                           # (Hh, Q)
        new = jnp.exp(total)[:, None, None] * state + es("shp,sn->hpn", rounded(xb * weight.T[:, :, None]), bm)
        state = jnp.where(live, new, state)
        old = jax.lax.dynamic_slice_in_dim(y, block * Q, Q, 0)
        y = jax.lax.dynamic_update_slice_in_dim(y, jnp.where((mine & live)[:, None, None], yb, old), block * Q, 0)
        entry = jnp.where(live & (last == 1), entry.at[slot].set(state), entry)
        return entry, state, y

    entry, _, y = jax.lax.fori_loop(0, meta.shape[0], visit,
                                    (entry, jnp.zeros(entry.shape[1:], jnp.float32), jnp.zeros_like(x)))
    return y[:T], jax.lax.dynamic_update_index_in_dim(store, entry, ordinal, 0)


def ssd_chunk(store, ordinal, x, dt, B, C, A, meta, *, Q: int, mm=jnp.float32, kernel: bool | None = None):
    """The rows of more than one token of a mixed step, blocked. store (state
    layers, slots, Hh, P, N) float32, ``ordinal`` the layer's entry; x (T, Hh,
    P), dt (T, Hh) after softplus, B and C (T, N) the packed tokens' operands;
    A (Hh,) negative; ``meta`` = block_plan(...)[0] at the same Q. Returns
    (y (T, Hh, P) float32 WITHOUT the D x term, defined at the tokens of rows
    with more than one token and arbitrary elsewhere; the store with those
    rows' slots at their last state). ``mm``: the type the block products'
    operands are rounded to (the model's activation type: float32 products run
    at the highest precision; bfloat16 operands are what every other product
    of a bfloat16 engine takes); sums, decays and the state are float32 either
    way. ``kernel`` None: the Pallas kernel on a TPU, the twin elsewhere; tests
    force the kernel, interpreted off the TPU."""
    with jax.named_scope("ssm.scan"):
        if kernel is None:
            kernel = on_tpu()
        if kernel:
            return ssd_chunk_tpu(store, ordinal, x, dt, B, C, A, meta, Q=Q, mm=mm, interpret=not on_tpu())
        return ssd_chunk_twin(store, ordinal, x, dt, B, C, A, meta, Q=Q, mm=mm)


def recurrence(state, x, dt, B, C, A):
    """The definition, a token at a time, for ONE sequence: state (Hh, P, N);
    x (T, Hh, P), dt (T, Hh), B and C (T, N). Returns (y (T, Hh, P) without
    the D x term, the last state). The tests' oracle."""
    def step(S, tok):
        xt, dtt, bt, ct = tok
        S = jnp.exp(dtt * A)[:, None, None] * S + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, ct, precision=HIGHEST)

    state, y = jax.lax.scan(step, state, (x, dt, B, C))
    return y, state
