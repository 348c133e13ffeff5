"""Selective state-space operations (Mamba, arXiv:2312.00752) for serving: a
depthwise causal convolution and a selective scan.

    c_t = silu(b + sum_j w[j] * u_{t-K+1+j})
    S_t = exp(Delta_t A) * S_{t-1} + (Delta_t c_t) B_t^T ;  y_t = S_t C_t + D * c_t

The state is float32 whatever the activations are: ``S`` a slot a layer, and
the convolution's tail, the last K - 1 inputs of a slot (slots, K - 1, Di).
The recurrence multiplies the state by exp(Delta * A) at every token for
thousands of tokens; in bfloat16 the product drifts by a rounding a step
(tests/test_phi4flash.py and tests/test_jamba.py hold the float32 state to
their references and show the bfloat16 one failing). A slot's state lies
``(N, Di)``, the state's N columns in the sublanes and the CHANNELS IN THE
LANES (ISSUE 64): an array whose minor axis is 16 is stored in whole tiles of
128 lanes on the chip, eight times its bytes, and so was every read and write
of it; the store of a layer stack is ``(state layers, slots, N, Di)``.

The scan has THREE forms:

- ``scan_decode``: every row is one slot advancing ONE token, batched over the
  slots. The decode chunk's rows and a mixed step's one-token rows take it. On a
  TPU the Pallas kernel ``ssm_scan_decode_kernel``: a walk of the rows that
  advance (``ops/ssd.walk_rows``), a row's state in and back out by DMA while its
  neighbours' move, the chunk kernel's step of one token on it; a slot that does
  not advance is never touched. Elsewhere the plain ``jax.numpy`` form over the
  layer's whole entry of the store.
- ``scan_chunk``: the rows of a mixed step that hold MORE than one token. On a
  TPU the Pallas kernel ``ssm_scan_chunk_kernel``: the packed token axis is cut
  into blocks of ``CHUNK_TOKENS`` from 0 and a row visits every block it
  touches, in order (``ops/ssd.block_plan``: the plan Mamba-2's blocked scan
  walks; a grid step a LIVE visit, ``ops/ssd.live_visits``); a row's first visit brings its slot's state (N, Di) into VMEM by DMA
  (zeros where the row starts at position 0), every visit walks its tokens
  there, eight at a time and 128 channels at a time, exp(Delta_t A) formed on
  the fly, and the last visit puts the state back in place. Nothing becomes a
  matrix product (the decay is a value a channel a state column): the body is
  the vector unit's and the transcendental unit's, about 17 vector operations
  and 2 exponentials a token a 128 channels. The tokens of a block that belong
  to ANOTHER row are identity steps (Delta = 0) whose outputs are not written.
  B and C come in replicated over the lanes, (tokens, N, 128), so that a
  token's column needs no transpose in the kernel. Elsewhere than on a TPU the
  same plan in plain ``jax.numpy`` (``scan_chunk_twin``).
- ``scan_ragged``: THE DEFINITION, a ``fori_loop`` trip a live packed token,
  each reading and writing its slot's state in the store. No step program
  calls it; the tests hold the kernel and the twin to it.

THE INVARIANT of the chunk form, carried over from the loop: a row run whole
and run in any chunks leaves the same state and the same y, to the bit, and a
row's result does not depend on its packed position or its neighbours: a
token's step is one fixed sequence of operations on its own operands (the sum
over N in one fixed order inside the token; tokens in order, no tree).

Every segment, a prompt chunk or a decode row of one token, starts from its
slot's state and leaves its last state there; a segment that starts at
position 0 starts from zero whatever the slot held, so a slot that is
admitted again, or resumed after a preemption by recompute, needs no reset
from the host.

The convolution has two forms, decode and ragged. It alone is also LFM2's
token mixer (models/mixtral.py, ISSUE 43: K = 3, no bias, no activation), so
both of its forms take the bias (``b`` None: none) and the activation (``act``
None: linear).

The ragged convolution reads what a slot remembers only where a segment
begins (ISSUE 53). A token with ``lag`` neighbours of its own segment in front
of it takes its input ``lag`` back from the packed array itself, at a static
shift; only a segment's first K - 1 tokens, at most slots x (K - 1) rows a
step, reach into the slot's tail, and they are computed apart, from
[tail ; the segment's first K - 1 inputs], and written over the shifted form's
rows. Two expressions therefore give one token's value, depending on where its
chunk begins, and THE INVARIANT is that they agree to the bit: a token's
float32 is a function of its K inputs alone, not of its packed position nor of
where the scheduler cut the prompt (a sequence run whole and run in chunks
leaves the same state; tests/test_ssm_conv_ragged.py). What keeps it: one
order of sums (``_taps``: the lead term, then lag 0, 1, 2, .., one addition
each) in both expressions, every addition with exactly ONE product beside it,
so that a compiler which contracts multiply-adds has one way to do it (XLA's
CPU backend does contract; of ``w0 * u0 + w1 * u1`` it may keep either
product rounded, and two fusions may choose differently: with no bias the
sum starts from a zero the compiler cannot fold).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from inference_gateway_tpu.ops.norms import rms_norm
from inference_gateway_tpu.ops.quant import qmatmul
from inference_gateway_tpu.ops.ssd import (META, RING, _padded, advancing_rows, block_plan, handed_back, live_visits, staged,
                                           walk_rows)
from inference_gateway_tpu.utils.platform import device_platform, on_tpu


def _activate(acc, b, act):
    """``act(b + acc)``; b None: no bias, act None: no activation."""
    acc = acc if b is None else b + acc
    return acc if act is None else act(acc)


def conv_decode(u, tails, w, b, valid, act=jax.nn.silu):
    """One token a slot. u (S, Di); tails (S, K-1, Di) float32, oldest first;
    w (K, Di); b (Di,) or None; valid (S,) rows that advance. Returns c (S, Di)
    float32 and the new tails."""
    with jax.named_scope("ssm.conv"):
        u = u.astype(jnp.float32)
        window = jnp.concatenate([tails, u[:, None, :]], axis=1)           # (S, K, Di)
        c = _activate(jnp.sum(window * w[None], axis=1), b, act)
        return c, jnp.where(valid[:, None, None], window[:, 1:], tails)


def scan_decode(states, delta, c, B, C, A, D, valid, fresh=None):
    """One token a slot. states (S, N, Di) float32; delta, c (S, Di); B, C
    (S, N); A (N, Di) negative; D (Di,); fresh (S,) rows whose token is at
    position 0 (they start from zero). Returns y (S, Di) and the new states;
    rows not ``valid`` keep theirs."""
    with jax.named_scope("ssm.scan"):
        prev = states if fresh is None else jnp.where(fresh[:, None, None], 0.0, states)
        new = jnp.exp(delta[:, None, :] * A[None]) * prev + (delta * c)[:, None, :] * B[:, :, None]
        y = jnp.sum(new * C[:, :, None], axis=1) + D * c
        return y, jnp.where(valid[:, None, None], new, states)


def token_segments(q_starts, q_lens, T: int):
    """Which slot each of T packed tokens belongs to, and its index inside
    its segment. Rows are packed back to back in any order of slots; a
    token past the last row belongs to none (slot = number of slots, which
    a scatter drops). Returns (slot (T,), local (T,), live (T,))."""
    t = jnp.arange(T)[:, None]
    member = (t >= q_starts[None, :]) & (t < (q_starts + q_lens)[None, :])  # (T, S)
    live = jnp.any(member, axis=1)
    slot = jnp.where(live, jnp.argmax(member, axis=1), q_starts.shape[0])
    local = jnp.arange(T) - q_starts[jnp.minimum(slot, q_starts.shape[0] - 1)]
    return slot.astype(jnp.int32), local.astype(jnp.int32), live


def _taps(tap, w, lead, act):
    """``act(lead + w[K-1] * tap(0) + w[K-2] * tap(1) + ...)``, summed left to
    right, one addition a lag, each with one product beside it: THE order of
    the ragged convolution's sums (``tap(lag)``: the input ``lag`` tokens back)."""
    K = w.shape[0]
    acc = lead
    for lag in range(K):
        acc = acc + w[K - 1 - lag] * tap(lag)
    return acc if act is None else act(acc)


def conv_ragged(u, tails, w, b, seg, fresh, q_starts, q_lens, act=jax.nn.silu):
    """The convolution over packed tokens. u (T, Di); tails (S, K-1, Di);
    ``seg`` = token_segments(...) (the callers' contract: the segments are read
    from ``q_starts`` / ``q_lens`` here); fresh (S,) slots whose segment starts at
    position 0 (their tail counts as zero); b (Di,) or None. Returns c (T, Di)
    float32 and the new tails: the last K - 1 inputs of each slot that had
    tokens, an empty slot's as they were. Rows past the live tokens hold anything.

    The BODY: every row's taps are the packed inputs shifted down by ``lag`` rows
    (zeros shifted in), right wherever the row has ``lag`` neighbours of its own
    segment in front of it. The HEADS: the first K - 1 rows of a segment, (K-1,
    S, Di) of them, see [the slot's tail ; the segment's first inputs] instead:
    head i of a slot takes input ``lag`` back from that window's entry
    K - 1 + i - lag, by the same ``_taps``, and is written over the body's row
    ``q_starts + i`` where ``i < q_lens`` (else to row T, which the scatter
    drops). The window's entries and the new tails are handled a (S, Di) slab at
    a time, the way the store lays them out (lag-major): no (T, Di) array of
    remembered inputs, no gather but of the 2 x (K-1) x S packed rows the heads
    and the new tails read. The module docstring has the invariant."""
    with jax.named_scope("ssm.conv"):
        del seg
        T, K, Di = u.shape[0], w.shape[0], u.shape[1]
        S, n = tails.shape[0], q_lens
        tails0 = jnp.where(fresh[:, None, None], 0.0, tails)
        old = [tails0[:, m] for m in range(K - 1)]                          # (S, Di) each, oldest first
        lead = jnp.minimum(n[0], 0).astype(jnp.float32) if b is None else b  # no bias: a zero no compiler folds
        uf = u.astype(jnp.float32)
        c = _taps(lambda lag: jnp.pad(uf[:T - lag], ((lag, 0), (0, 0))), w, lead, act)
        j = jnp.arange(K - 1)[:, None]
        rows = q_starts[None, :] + j                                        # (K-1, S): head i of slot s is packed row rows[i, s]

        def packed(at):   # (R, S) packed rows -> their inputs (R, S, Di); one off the array reads a neighbour, unused
            return u[jnp.clip(at, 0, T - 1).reshape(-1)].astype(jnp.float32).reshape(*at.shape, Di)

        taken = packed(jnp.concatenate([rows, rows + n[None, :] - (K - 1)]))   # the segments' first K - 1 rows, and their last
        first, last = taken[:K - 1], taken[K - 1:]
        window = old + [first[i] for i in range(K - 1)]                     # a slot's tail, then its first inputs
        heads = jnp.stack([_taps(lambda lag: window[K - 1 + i - lag], w, lead, act) for i in range(K - 1)])
        c = c.at[jnp.where(j < n[None, :], rows, T).reshape(-1)].set(heads.reshape(-1, Di), mode="drop")
        # New tail entry m of a slot is its input at segment index n - (K-1) + m; before the segment, tail entry m + n.
        new = []
        for m in range(K - 1):
            kept = old[m]
            for k in range(1, K - 1 - m):
                kept = jnp.where((n == k)[:, None], old[m + k], kept)
            new.append(jnp.where((n >= K - 1 - m)[:, None], last[m], kept))
        return c, jnp.where((n > 0)[:, None, None], jnp.stack(new, axis=1), tails)


def scan_ragged(states, delta, c, B, C, A, D, seg, positions, n_tokens):
    """THE DEFINITION of the selective scan over packed tokens, one after
    another: token t reads the state its slot holds (zero where its position is
    0), advances it and puts it back, so a segment's tokens chain through the
    store and the store ends with every slot's last state. states (S, N, Di)
    float32; delta, c (T, Di); B, C (T, N); A (N, Di); ``seg`` =
    token_segments(...); positions (T,); n_tokens: how many packed tokens are
    live (they are packed from 0). Returns y (T, Di) and the new states."""
    with jax.named_scope("ssm.scan"):
        slot = seg[0]
        S = states.shape[0]

        def step(t, carry):
            states, y = carry
            s = jnp.minimum(slot[t], S - 1)        # every token the loop visits is live
            prev = jnp.where(positions[t] == 0, 0.0, jax.lax.dynamic_index_in_dim(states, s, 0, keepdims=False))
            new = jnp.exp(delta[t][None, :] * A) * prev + (delta[t] * c[t])[None, :] * B[t][:, None]
            y = jax.lax.dynamic_update_index_in_dim(y, jnp.sum(new * C[t][:, None], axis=0) + D * c[t], t, 0)
            return jax.lax.dynamic_update_index_in_dim(states, new, s, 0), y

        states, y = jax.lax.fori_loop(0, n_tokens, step, (states, jnp.zeros_like(c)))
        return y, states


CHUNK_TOKENS = 64   # tokens a visit of the chunk kernel holds: its blocks of Delta, c and y are 64 x Di float32 of VMEM
TILE, LANES = 8, 128   # the kernel walks eight tokens (a tile's sublanes) of 128 channels (its lanes) at a time


def chunk_plan(Di: int, N: int) -> dict:
    """How a mixed step's selective scan walks a state of (N, Di): ``path`` is
    the chunk kernel's name on a TPU where Di is whole lane tiles, else the
    twin's with the ``reason``; the tokens a visit holds, the channels the state
    in VMEM spans (all of them: one grid step a visit; ``grid``: the plan's live
    visits are the grid's steps, ISSUE 65), and the form a one-token
    row takes (the one-token kernel under the same condition, else
    ``scan_decode``). ``/debug/status`` reports it."""
    plan = {"token_block": CHUNK_TOKENS, "channel_block": Di, "walks": f"{TILE} tokens x {LANES} channels",
            "one_token_rows": "scan_decode", "state_in_vmem_bytes": N * Di * 4}
    if not on_tpu():
        return {**plan, "path": "scan_chunk_twin", "reason": f"platform {device_platform()} is not TPU"}
    if Di % LANES:
        return {**plan, "path": "scan_chunk_twin", "reason": f"{Di} channels are not whole tiles of {LANES} lanes"}
    return {**plan, "path": "ssm_scan_chunk_kernel", "grid": "live visits", "one_token_rows": "ssm_scan_decode_kernel",
            "reason": "a row's state in VMEM across its visits"}


def _scan_chunk_kernel(ord_ref, meta_ref, d_ref, x_ref, b_ref, c_ref, a_ref, dd_ref, store_in,
                       y_ref, store_out, s_ref, sem, *, Q: int, Di: int):
    j = pl.program_id(0)
    word = lambda k: meta_ref[j * META + k]
    slot, lo, hi = word(1), word(2), word(3)
    first, last, fresh, init = word(4) == 1, word(5) == 1, word(6) == 1, word(7) == 1
    live = hi > lo
    mine_at = lambda ref: ref.at[ord_ref[0], slot]

    @pl.when(init)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

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
        row = jax.lax.broadcasted_iota(jnp.int32, (TILE, LANES), 0)

        def group(g, _):   # 128 channels: their state stays in registers across the visit's tokens
            lanes = pl.ds(pl.multiple_of(g * LANES, LANES), LANES)
            A, D = a_ref[:, lanes], dd_ref[:, lanes]                            # (N, 128), (1, 128)

            def tile(tg, S):
                t0 = pl.multiple_of(tg * TILE, TILE)
                toks = pl.ds(t0, TILE)
                mine = (row + t0 >= lo) & (row + t0 < hi)
                x = x_ref[toks, lanes]
                d = jnp.where(mine, d_ref[toks, lanes], 0.0)                    # another row's token: an identity step
                dx = d * x
                y = jnp.zeros((TILE, LANES), jnp.float32)
                for i in range(TILE):
                    S = jnp.exp(d[i:i + 1, :] * A) * S + dx[i:i + 1, :] * b_ref[t0 + i]
                    y = jnp.where(row == i, jnp.sum(S * c_ref[t0 + i], axis=0, keepdims=True), y)
                y_ref[toks, lanes] = jnp.where(mine, y + D * x, y_ref[toks, lanes])
                return S

            s_ref[:, lanes] = jax.lax.fori_loop(0, Q // TILE, tile, s_ref[:, lanes])

        jax.lax.fori_loop(0, Di // LANES, group, None)

    @pl.when(live & last)
    def _():
        save = pltpu.make_async_copy(s_ref, mine_at(store_out), sem.at[1])
        save.start()
        save.wait()


@functools.partial(jax.jit, static_argnames=("Q", "interpret"))
def scan_chunk_tpu(store, ordinal, delta, c, B, C, A, D, meta, *, Q: int = CHUNK_TOKENS, interpret: bool = False):
    """``scan_chunk`` by the Pallas kernel. The store is updated in place."""
    T, Di = delta.shape
    N = B.shape[1]
    Tp, (delta, c, B, C) = _padded(T, Q, delta, c, B, C)
    spread = lambda a: jnp.broadcast_to(a[:, :, None], (Tp, N, LANES))          # a token's column, over the lanes
    at = lambda k: (lambda j, o, m: k(m[j * META]))
    tokens = pl.BlockSpec((Q, Di), at(lambda b: (b, 0)))
    columns = pl.BlockSpec((Q, N, LANES), at(lambda b: (b, 0, 0)))
    whole = lambda rows: pl.BlockSpec((rows, Di), lambda j, o, m: (0, 0))
    y, store = pl.pallas_call(
        functools.partial(_scan_chunk_kernel, Q=Q, Di=Di),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(live_visits(meta),),
            in_specs=[tokens, tokens, columns, columns, whole(N), whole(1), pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=[tokens, pl.BlockSpec(memory_space=pltpu.ANY)],
            scratch_shapes=[pltpu.VMEM((N, Di), jnp.float32), pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((Tp, Di), jnp.float32), jax.ShapeDtypeStruct(store.shape, store.dtype)],
        input_output_aliases={8: 1},   # the store: operand 8 counting the two scalar operands
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ssm_scan_chunk_kernel",
    )(jnp.reshape(ordinal, (1,)).astype(jnp.int32), meta.reshape(-1), delta, c, spread(B), spread(C),
      A.astype(jnp.float32), D.astype(jnp.float32).reshape(1, Di), store)
    return y[:T], store


def scan_chunk_twin(store, ordinal, delta, c, B, C, A, D, meta, *, Q: int = CHUNK_TOKENS):
    """``scan_chunk`` in plain ``jax.numpy``: the same visits, one after
    another, a visit's tokens by ``lax.scan``."""
    T = delta.shape[0]
    Tp, (delta, c, B, C) = _padded(T, Q, delta, c, B, C)
    entry = jax.lax.dynamic_index_in_dim(store, ordinal, 0, keepdims=False)    # (S, N, Di)

    def visit(j, carry):
        entry, state, y = carry
        block, slot, lo, hi, first, last, fresh, _ = (meta[j, k] for k in range(META))
        live = hi > lo
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, block * Q, Q, 0)
        mine = (jnp.arange(Q) >= lo) & (jnp.arange(Q) < hi)
        db, xb = jnp.where(mine[:, None], take(delta), 0.0), take(c)
        state = jnp.where((first == 1) & (fresh == 0), entry[slot], jnp.where(first == 1, 0.0, state))

        def step(S, tok):
            d, x, b, cc = tok
            S = jnp.exp(d[None, :] * A) * S + (d * x)[None, :] * b[:, None]
            return S, jnp.sum(S * cc[:, None], axis=0) + D * x

        new, yb = jax.lax.scan(step, state, (db, xb, take(B), take(C)))
        state = jnp.where(live, new, state)
        old = jax.lax.dynamic_slice_in_dim(y, block * Q, Q, 0)
        y = jax.lax.dynamic_update_slice_in_dim(y, jnp.where((mine & live)[:, None], yb, old), block * Q, 0)
        entry = jnp.where(live & (last == 1), entry.at[slot].set(state), entry)
        return entry, state, y

    entry, _, y = jax.lax.fori_loop(0, meta.shape[0], visit,
                                    (entry, jnp.zeros(entry.shape[1:], jnp.float32), jnp.zeros_like(delta)))
    return y[:T], jax.lax.dynamic_update_index_in_dim(store, entry, ordinal, 0)


def scan_chunk(store, ordinal, delta, c, B, C, A, D, meta):
    """The rows of more than one token of a mixed step. store (state layers,
    slots, N, Di) float32, ``ordinal`` the layer's entry; delta (after
    softplus) and c (T, Di), B and C (T, N) the packed tokens' operands; A (N,
    Di) negative; D (Di,); ``meta`` = ops/ssd.block_plan(...)[0] at
    CHUNK_TOKENS. Returns (y (T, Di) float32, defined at the tokens of rows with
    more than one token and arbitrary elsewhere; the store with those rows'
    slots at their last state): by the Pallas kernel where ``chunk_plan`` says
    so, by the twin elsewhere."""
    with jax.named_scope("ssm.scan"):
        if chunk_plan(delta.shape[1], B.shape[1])["path"] == "ssm_scan_chunk_kernel":
            return scan_chunk_tpu(store, ordinal, delta, c, B, C, A, D, meta)
        return scan_chunk_twin(store, ordinal, delta, c, B, C, A, D, meta)


def ragged_scan(q_starts, q_lens, kv_lens, T: int):
    """A mixed step's selective scan over its packed rows (row r = slot r), for
    every Mamba layer of the step: a row of more than one token through
    ``scan_chunk``, a row of one token through ``scan_decode`` at its packed
    position; each from its slot's state, from zero where it starts at position
    0. Returns (``scan(store, ordinal, delta, c, B, C, A, D) -> (y, store)``, the
    tokens' segments, fresh (S,), and the step's counts, float32 (4,): the tokens
    and the rows the chunk form walked, the rows the one-token form served, and
    the static bound of the chunk plan's visits, of which the kernel's grid runs
    the live ones)."""
    seg, fresh = token_segments(q_starts, q_lens, T), (kv_lens - q_lens) == 0
    slot, _, live = seg
    one, more = q_lens == 1, q_lens > 1
    in_chunk = live & more[jnp.minimum(slot, q_lens.shape[0] - 1)]
    meta, _ = block_plan(q_starts, q_lens, kv_lens, T, CHUNK_TOKENS)
    first = jnp.minimum(q_starts, T - 1)

    def scan(store, ordinal, delta, c, B, C, A, D):
        y, store = scan_chunk(store, ordinal, delta, c, B, C, A, D, meta)
        y1, store = _decode_entry(store, ordinal, delta[first], c[first], B[first], C[first], A, D, one, fresh)
        return jnp.where(in_chunk[:, None], y, 0.0).at[jnp.where(one, q_starts, T)].set(y1, mode="drop"), store

    counts = jnp.stack([jnp.sum(jnp.where(more, q_lens, 0)), jnp.sum(more), jnp.sum(one), meta.shape[0]]).astype(jnp.float32)
    return scan, seg, fresh, counts


DECODE_LANES = 1024   # channels the one-token kernel advances at a time: (N, 1024) float32 of a state are 16 registers


def _scan_decode_kernel(n_ref, rows_ref, ord_ref, fresh_ref, d_hbm, x_hbm, b_hbm, c_hbm, a_hbm, dd_hbm, store_in,
                        y_hbm, store_out, ring, sem, d_ref, x_ref, b_ref, c_ref, a_ref, dd_ref, y_ref, row_sem, *, W: int):
    y_ref[...] = jnp.zeros_like(y_ref)
    staged((d_hbm, x_hbm, b_hbm, c_hbm, a_hbm, dd_hbm), (d_ref, x_ref, b_ref, c_ref, a_ref, dd_ref), row_sem)
    over = lambda col: jnp.concatenate([col] * (W // LANES), axis=1)               # a column (N, 128) over W lanes

    def advance(slot, _, ref):   # ref (N, Di): the slot's state, in VMEM
        fresh, row = fresh_ref[slot] == 1, pl.ds(slot, 1)
        b, cc = over(b_ref[slot]), over(c_ref[slot])
        # The chunk kernel's step of ONE token, W channels at a time at STATIC lanes: few and wide operations, so the
        # body is a few dozen equations to lower at every start and straight-line code on the chip.
        for lanes in (pl.ds(at, W) for at in range(0, ref.shape[1], W)):
            d, x, state = d_ref[row, lanes], x_ref[row, lanes], ref[:, lanes]       # (1, W), (1, W), (N, W)
            prev = jnp.where(fresh, jnp.zeros_like(state), state)
            new = jnp.exp(d * a_ref[:, lanes]) * prev + (d * x) * b
            ref[:, lanes] = new
            y_ref[row, lanes] = jnp.sum(new * cc, axis=0, keepdims=True) + dd_ref[:, lanes] * x

    walk_rows(n_ref, rows_ref, ord_ref, store_in, store_out, ring, sem, visits=1, rows=ring.shape[1], advance=advance)
    handed_back(y_ref, y_hbm, row_sem.at[6])


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_decode_tpu(store, ordinal, delta, c, B, C, A, D, valid, fresh, *, interpret: bool = False):
    """``scan_decode`` on entry ``ordinal`` of the store (state layers, slots, N,
    Di) by the Pallas kernel ``ssm_scan_decode_kernel``: one program that walks
    the rows that advance (``ops/ssd.walk_rows``, the walk Mamba-2's and KDA's
    one-token kernels share), a row's state (N, Di) in and back out by DMA, in
    place, while the next comes in and the one before goes back; a slot that does
    not advance costs nothing and keeps its bits. A token's step is the chunk
    kernel's, operation for operation. The slots' row operands and y stand in
    VMEM whole; B and C come in replicated over the lanes, (slots, N, 128), as
    the chunk kernel takes them. Returns y (S, Di), zero at the rows that do not
    advance, and the store."""
    L, S, N, Di = store.shape
    order, n = advancing_rows(valid)
    spread = lambda a: jnp.broadcast_to(a.astype(jnp.float32)[:, :, None], (S, N, LANES))
    smem, hbm = (pl.BlockSpec(memory_space=m) for m in (pltpu.SMEM, pltpu.ANY))
    held = lambda *shape: pltpu.VMEM(shape, jnp.float32)
    y, store = pl.pallas_call(
        functools.partial(_scan_decode_kernel, W=DECODE_LANES if Di % DECODE_LANES == 0 else Di),
        in_specs=[smem] * 4 + [hbm] * 7,
        out_specs=[hbm, hbm],
        scratch_shapes=[held(RING, N, Di), pltpu.SemaphoreType.DMA((2, RING)), held(S, Di), held(S, Di),
                        held(S, N, LANES), held(S, N, LANES), held(N, Di), held(1, Di), held(S, Di),
                        pltpu.SemaphoreType.DMA((7,))],
        out_shape=[jax.ShapeDtypeStruct((S, Di), jnp.float32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        input_output_aliases={10: 1},   # the store
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="ssm_scan_decode_kernel",
    )(n, order, jnp.reshape(ordinal, (1,)).astype(jnp.int32), fresh.astype(jnp.int32), delta.astype(jnp.float32),
      c.astype(jnp.float32), spread(B), spread(C), A.astype(jnp.float32), D.astype(jnp.float32).reshape(1, Di), store)
    return y, store


def _decode_entry(store, ordinal, delta, c, B, C, A, D, rows, fresh=None):
    """The one-token form on the layer's entry of the store (state layers, slots,
    N, Di): the kernel where ``chunk_plan`` names it, else ``scan_decode``."""
    if chunk_plan(store.shape[3], store.shape[2])["one_token_rows"] == "ssm_scan_decode_kernel":
        with jax.named_scope("ssm.scan"):
            return scan_decode_tpu(store, ordinal, delta, c, B, C, A, D, rows, jnp.zeros_like(rows) if fresh is None else fresh)
    entry = jax.lax.dynamic_index_in_dim(store, ordinal, 0, keepdims=False)
    y, entry = scan_decode(entry, delta, c, B, C, A, D, rows, fresh)
    return y, jax.lax.dynamic_update_index_in_dim(store, entry, ordinal, 0)


def decode_scan(rows):
    """The same for the decode chunk: one token a slot; ``rows`` (S,) the slots
    that carry a stream (the others keep their state)."""
    return lambda store, ordinal, *operands: _decode_entry(store, ordinal, *operands, rows)


def mamba_mixer(h, mp, store, ordinal, conv, scan, eps: float = 1e-6):
    """The Mamba-1 mixer on rows h (T, H): ONE body for every model that has it
    (models/phi4flash.py, models/jamba.py). ``mp``: its leaves, ``in_proj`` (H,
    2 Di), ``conv_w`` (K, Di), ``conv_b``, ``x_proj`` (Di, R + 2 N), ``dt_proj``
    (R, Di), ``dt_bias``, ``A_log`` (Di, N), ``D``, ``out_proj`` (Di, H), and
    where the model has them (Jamba's ``dt_layernorm`` / ``b_layernorm`` /
    ``c_layernorm``) the gains ``dt_norm`` (R,), ``b_norm`` and ``c_norm`` (N,) of
    RMSNorms at ``eps`` over Delta's bottleneck, B and C. ``store``: {"ssm":
    (layers, slots, N, Di), "conv": (layers, slots, K - 1, Di)}, the layer's
    entries at ``ordinal``; ``conv(u, tails, w, b) -> (c, tails)`` and
    ``scan(ssm store, ordinal, delta, c, B, C, A, D) -> (y, ssm store)`` are the
    step's forms of the two state operations. Returns (out (T, H), y (T, Di)
    float32 before the gate, the store)."""
    N = mp["A_log"].shape[-1]
    R = mp["x_proj"].shape[-1] - 2 * N
    u, z = jnp.split(qmatmul(h, mp["in_proj"]), 2, axis=-1)
    tails = jax.lax.dynamic_index_in_dim(store["conv"], ordinal, 0, keepdims=False)
    c, tails = conv(u, tails, mp["conv_w"], mp["conv_b"])
    d, B, C = jnp.split(qmatmul(c.astype(h.dtype), mp["x_proj"]).astype(jnp.float32), [R, R + N], axis=-1)
    if "dt_norm" in mp:
        d, B, C = (rms_norm(a, mp[g], eps) for a, g in ((d, "dt_norm"), (B, "b_norm"), (C, "c_norm")))
    delta = jax.nn.softplus(qmatmul(d.astype(h.dtype), mp["dt_proj"]).astype(jnp.float32) + mp["dt_bias"])
    y, ssm = scan(store["ssm"], ordinal, delta, c, B, C, -jnp.exp(mp["A_log"]).T, mp["D"])
    with jax.named_scope("ssm.gate"):
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
    out = qmatmul(gated, mp["out_proj"])
    return out, y, {"ssm": ssm, "conv": jax.lax.dynamic_update_index_in_dim(store["conv"], tails, ordinal, 0)}
