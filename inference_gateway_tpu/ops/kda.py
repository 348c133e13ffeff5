"""Kimi Delta Attention (KDA, arXiv:2510.26692) for serving: a linear
attention whose state is a MATRIX a head, decayed by a VECTOR a head a token
(one factor a key channel) and corrected by a rank-one delta rule (ISSUE 54).
Beside ``ops/ssd.py`` (Mamba-2: one scalar decay a head, no correction), whose
plan of visits over the packed axis this file shares, and ``ops/ssm.py``, whose
convolution the mixer shares.

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T ;   o_t = S_t^T q_t

a head: q_t, k_t (K,) (the caller has normalised them), v_t (V,), a_t =
exp(g_t) in (0, 1)^K, beta_t in (0, 1), S (K, V) float32. A slot's state is
``(heads, K, V)``; the store of a layer stack ``(state layers, slots, heads, K, V)``.
The one-token form: ``S' = Diag(a_t) S; u_t = beta_t (v_t - S'^T k_t); S_t = S' + k_t u_t^T``.

Two forms, as ``ops/ssd.py`` has them:

- ``update_store``: every row is one slot advancing ONE token, batched over the
  slots. On a TPU it is the Pallas kernel ``kda_state_update_kernel``:
  ``ops/ssd.walk_rows``'s walk of the rows that advance, a visit moving about
  a megabyte of ONE row's states in and back out by DMA, in place; a slot that
  does not advance is never visited. Elsewhere ``state_update``, the
  definition in plain ``jax.numpy`` over the layer's whole entry.
- ``kda_chunk``: the rows of a mixed step that hold MORE than one token, in
  blocks of Q tokens. With G_t the running sum of g inside the block (a vector),

      (I + L) U = Diag(beta) (V - (K * e^G) S_in),   L[t, s] = beta_t sum_c k_t[c] k_s[c] e^{G_t[c] - G_s[c]}, s < t
      O = (Q * e^G) S_in + A U,                      A[t, s] = sum_c q_t[c] k_s[c] e^{G_t[c] - G_s[c]}, s <= t
      S_out = Diag(e^{G_Q}) S_in + (K * e^{G_Q - G})^T U

  In exact arithmetic that IS the recurrence whatever Q. The decay differs by
  channel, so L and A are not one product of two scaled operands: ``K e^{G}``
  against ``K e^{-G}`` overflows float32 once a channel has decayed by e^88
  inside a block. A block is therefore HALVED (ISSUE 55): the rows of its
  second half against the columns of its first are ONE product of operands
  scaled about the second half's first token r (``e^{G_t - G_r}`` and ``e^{G_r
  - G_s}``: both exponents <= 0), and so again inside each half, down to
  single tokens: log2 Q levels, ONE product at each (the columns' operand
  against the rows' for L and for A side by side), masked to the level's
  squares, which tile the strict lower triangle; A's diagonal is ``q_t .
  k_t``. No exponent that is formed is positive and no pair is formed alone. The two exponents of a level are sums of g INSIDE a half
  (``_halved``), never the difference of two block-long running sums, so a
  pair's decay keeps float32's digits at any decay strength. The unit
  triangular system is solved by the product ``(I - L)(I + L^2)(I + L^4) ..``
  (L is nilpotent: log2 Q factors, every one a plain product).

**How the blocks lie**: ``ops/ssd.block_plan``'s visits, unchanged. A row of
more than one token visits every packed block it touches; the tokens of
another row in that block are identity steps (g = 0, beta = 0) whose outputs
are not written.

On a TPU ``kda_chunk`` is the Pallas kernel ``kda_chunk_kernel``: a grid of
(head blocks, the plan's live visits: ``ops/ssd.live_visits``), the head block's states in a VMEM scratch across a
row's visits, the store read and written in place by DMA at (layer ordinal,
slot). A head's key width is the 128 lanes, so tokens lie in the sublanes and
every operand is a plain blocked read. The products with the state and with
U take operands rounded to the model's activation type (``mm``; A is
rounded to it once formed); the sums of g, the decays, L and A (a level's
one product is float32: as precise as a pair formed alone), the solve, the
accumulation and the state are float32 (``ops/ssd.py``'s rule). The heads of
a head block go a phase at a time (every head's L and A, the solve a step at
a time over the heads, the outputs): a head's products wait on one another,
and the other heads' fill the matrix unit meanwhile. That unit's time
follows the ROWS it is fed, so whole registers of rows that are zero by
construction stay out of the products: the second halves' columns at the
levels of 8 tokens and wider, and the first rows of L's powers in the solve. Elsewhere ``kda_chunk`` is the same plan walked by
``lax.fori_loop`` in plain ``jax.numpy`` with every pair formed alone (the
twin; tests hold the interpreted kernel, and its L and A themselves, to it
and both to the recurrence).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from inference_gateway_tpu.ops.ssd import (HIGHEST, META, RING, _padded, advancing_rows, handed_back, live_visits, staged,
                                           visit_heads, visit_plan, walk_rows)
from inference_gateway_tpu.utils.platform import on_tpu

HEAD_BLOCK = 4   # heads a grid step of the chunk kernel holds: their states are 4 x K x V float32 of VMEM (256 KB at 128 x 128)


def state_update(entry, q, k, v, g, beta, valid, fresh=None):
    """One token a slot. entry (S, Hh, K, V) float32, the layer's states; q, k,
    g (S, Hh, K) (g the log of the decay, <= 0); v (S, Hh, V); beta (S, Hh);
    valid (S,) rows that advance (the others keep their state); fresh (S,) rows
    whose token is at position 0 (they start from zero). Returns o (S, Hh, V)
    float32 and the new entry. Plain ``jax.numpy``: the definition, and what
    runs off the TPU."""
    with jax.named_scope("ssm.scan"):
        prev = entry if fresh is None else jnp.where(fresh[:, None, None, None], 0.0, entry)
        decayed = jnp.exp(g)[..., None] * prev
        u = beta[..., None] * (v - jnp.sum(decayed * k[..., None], axis=2))
        new = decayed + k[..., None] * u[:, :, None, :]
        o = jnp.sum(new * q[..., None], axis=2)
        return o, jnp.where(valid[:, None, None, None], new, entry)


def update_plan(shape, platform: str | None = None) -> dict:
    """How ``update_store`` advances a store of ``shape`` (state layers, slots,
    Hh, K, V), as ``ops/ssd.update_plan`` says it of Mamba-2's."""
    _, _, Hh, K, V = shape
    off = None if K == V == 128 else f"a head's state of {K} x {V} is not the 128 x 128 square"
    return visit_plan(Hh, K, V, "kda_state_update_kernel", platform, off)


def _state_update_kernel(n_ref, rows_ref, ord_ref, fresh_ref, beta_ref, q_hbm, k_hbm, a_hbm, v_hbm, store_in, o_hbm, store_out,
                         ring, sem, q_ref, k_ref, a_ref, v_ref, o_ref, row_sem, *, hb: int, K: int, heads: int):
    o_ref[...] = jnp.zeros_like(o_ref)
    staged((q_hbm, k_hbm, a_hbm, v_hbm), (q_ref, k_ref, a_ref, v_ref), row_sem)

    def advance(slot, piece, ref):
        fresh = fresh_ref[slot] == 1
        V = ref.shape[1]
        # A head's (1, K) row as a COLUMN beside its state's rows: spread over the sublanes, turned.
        col = lambda row: jnp.broadcast_to(row, (V, K)).T

        def one(i, _):
            head = piece * hb + i
            mine = lambda r: r[slot, pl.ds(head, 1), :]                             # (1, K) or (1, V)
            rows = pl.ds(pl.multiple_of(i * K, 8), K)
            state = ref[rows, :]                                                    # (K, V)
            decayed = col(mine(a_ref)) * jnp.where(fresh, jnp.zeros_like(state), state)
            kc = col(mine(k_ref))
            u = beta_ref[slot * heads + head] * (mine(v_ref) - jnp.sum(decayed * kc, axis=0, keepdims=True))   # (1, V)
            new = decayed + kc * u
            ref[rows, :] = new
            o_ref[slot, pl.ds(head, 1), :] = jnp.sum(new * col(mine(q_ref)), axis=0, keepdims=True)
            return 0

        jax.lax.fori_loop(0, hb, one, 0, unroll=True)      # (the heads' turns wait on nothing of one another's)

    walk_rows(n_ref, rows_ref, ord_ref, store_in, store_out, ring, sem, visits=heads // hb, rows=hb * K, advance=advance)
    handed_back(o_ref, o_hbm, row_sem.at[4])


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_update_tpu(store, ordinal, q, k, v, g, beta, valid, fresh, *, interpret: bool = False):
    """``state_update`` on the whole store by the Pallas kernel
    ``kda_state_update_kernel`` (``ops/ssd.walk_rows``: one program over the
    rows that advance, ``visit_heads`` heads' states of one row a visit, in and
    back out by DMA): the store's traffic is the advancing rows' states, once in
    and once out. Returns o (S, Hh, V), zero at the rows that do not advance,
    and the store."""
    L, S, Hh, K, V = store.shape
    hb = visit_heads(Hh, K, V)
    rows = store.reshape(L, S, Hh * K, V)
    order, n = advancing_rows(valid)
    f32 = lambda a: a.astype(jnp.float32)
    smem, hbm = (pl.BlockSpec(memory_space=m) for m in (pltpu.SMEM, pltpu.ANY))
    held = lambda *shape: pltpu.VMEM(shape, jnp.float32)
    o, rows = pl.pallas_call(
        functools.partial(_state_update_kernel, hb=hb, K=K, heads=Hh),
        in_specs=[smem] * 5 + [hbm] * 5,
        out_specs=[hbm, hbm],
        scratch_shapes=[held(RING, hb * K, V), pltpu.SemaphoreType.DMA((2, RING)),
                        *[held(S, Hh, K)] * 3, held(S, Hh, V), held(S, Hh, V), pltpu.SemaphoreType.DMA((5,))],
        out_shape=[jax.ShapeDtypeStruct((S, Hh, V), jnp.float32), jax.ShapeDtypeStruct(rows.shape, rows.dtype)],
        input_output_aliases={9: 1},   # the store
        interpret=interpret,
        name="kda_state_update_kernel",
    )(n, order, jnp.reshape(ordinal, (1,)).astype(jnp.int32), fresh.astype(jnp.int32), f32(beta).reshape(-1),
      f32(q), f32(k), f32(jnp.exp(g)), f32(v), rows)
    return o, rows.reshape(store.shape)


def update_store(store, ordinal, q, k, v, g, beta, valid, fresh=None, kernel: bool | None = None):
    """``state_update`` on entry ``ordinal`` of the store (state layers, slots,
    Hh, K, V). Returns (o (S, Hh, V), defined at the valid rows; the store).
    ``kernel`` None: what ``update_plan`` says of the store's shape and the
    platform (the Pallas kernel on a TPU where a head's state is 128 x 128,
    plain ``jax.numpy`` elsewhere); tests force the kernel, interpreted off the
    TPU."""
    if kernel is None:
        kernel = update_plan(store.shape)["path"] != "state_update"
    if kernel:
        fresh = jnp.zeros_like(valid) if fresh is None else fresh
        with jax.named_scope("ssm.scan"):
            return state_update_tpu(store, ordinal, q, k, v, g, beta, valid, fresh, interpret=not on_tpu())
    entry = jax.lax.dynamic_index_in_dim(store, ordinal, 0, keepdims=False)
    o, entry = state_update(entry, q, k, v, g, beta, valid, fresh)
    return o, jax.lax.dynamic_update_index_in_dim(store, entry, ordinal, 0)


def _nt(a, b, **kw):
    """a (M, K) against b (N, K): a b^T, float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, **kw)


def _under(top: int, low):
    """``low`` under ``top`` rows of zeros (whole registers of them: a concatenation, no product)."""
    return jnp.concatenate([jnp.zeros((top,) + low.shape[1:], low.dtype), low], axis=0) if top else low


def _levels(Q: int):
    """The halving of a block of Q tokens (a power of two), widest last: for h
    in 1, 2, .., Q/2 the mask of level h's squares (the rows t of a second half
    of h tokens against the columns s of the first half in front of it; over
    the levels they tile the strict lower triangle), TURNED and twice side by
    side, (s, [t | t]) of (Q, 2Q), as ``_halved`` forms L and A; and the (Q, 1)
    mask of the tokens of second halves."""
    tok = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    s_of = jax.lax.broadcasted_iota(jnp.int32, (Q, 2 * Q), 0)
    t_of = jax.lax.broadcasted_iota(jnp.int32, (Q, 2 * Q), 1) % Q
    return [(h, (t_of // h == s_of // h + 1) & (t_of // h % 2 == 1), tok // h % 2 == 1)
            for h in (1 << n for n in range(Q.bit_length() - 1))]


def _halved(q, k, kb, g, levels):
    """L and A of one head's block inside the kernel, no pair formed alone: q,
    k, kb (= beta k), g (Q, K) float32 with foreign tokens at g = 0 and kb = 0.
    Level h is ONE float32 product about each second half's first token r: the
    columns' ``k_s e^{G_r - G_s}`` against the rows' ``kb_t e^{G_t - G_r}`` and
    ``q_t e^{G_t - G_r}`` side by side (L and A turned, 2Q lanes wide: the
    matrix unit's whole width). With b the first token of t's aligned run of h
    tokens, d[t] = g_{b+1} + .. + g_t and e[t] = g_{t+1} + .. + g_{b+h} are
    those two exponents at level h; d + e is the same all over a run, so the
    next level's d and e are two sublane shifts of it, summed inside a half
    and never a difference of two long sums. Returns (L strictly lower, A
    lower, d = G - g_0, e = G_Q - G)."""
    Q = q.shape[0]
    tok = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    d = jnp.zeros_like(g)
    e = jnp.where(tok < Q - 1, pltpu.roll(g, Q - 1, 0), 0.0)                   # g of the token behind
    turned = jnp.zeros((Q, 2 * Q), jnp.float32)
    for h, squares, back in levels:
        front, kc = jnp.exp(d), k * jnp.exp(e)
        rows = jnp.concatenate([kb * front, q * front], axis=0)
        if h % 8:
            level = _nt(kc, rows, precision=HIGHEST)
        else:   # whole registers: the first halves' columns alone are fed to the product, the others read zero
            live = _nt(jnp.concatenate([kc[b:b + h] for b in range(0, Q, 2 * h)], axis=0), rows, precision=HIGHEST)
            gap = jnp.zeros((h, 2 * Q), jnp.float32)
            level = jnp.concatenate([part for n in range(0, Q // 2, h) for part in (live[n:n + h], gap)], axis=0)
        turned = jnp.where(squares, level, turned)
        run = d + e
        d = d + jnp.where(back, pltpu.roll(run, h, 0), 0.0)
        e = e + jnp.where(back, 0.0, pltpu.roll(run, Q - h, 0))
    both = turned.T                                                            # (2Q, Q): L's rows, then A's
    diagonal = tok == jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return both[:Q], jnp.where(diagonal, jnp.sum(q * k, axis=1, keepdims=True), both[Q:]), d, e


def _kda_chunk_kernel(ord_ref, meta_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref, store_in, o_ref, store_out,
                      s_ref, sem, *, hb: int, Q: int, K: int, mm):
    h, j = pl.program_id(0), pl.program_id(1)
    word = lambda w: meta_ref[j * META + w]
    slot, lo, hi = word(1), word(2), word(3)
    first, last, fresh, init = word(4) == 1, word(5) == 1, word(6) == 1, word(7) == 1
    live = hi > lo
    mine_at = lambda ref: ref.at[ord_ref[0], slot, pl.ds(h * hb * K, hb * K)]
    exact = dict(precision=HIGHEST)
    rounded = dict(precision=HIGHEST) if mm == jnp.float32 else {}
    dot = lambda a, b, kw: jnp.dot(a, b, preferred_element_type=jnp.float32, **kw)

    @pl.when(init)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

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
        tok = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
        mine = (tok >= lo) & (tok < hi)                                        # (Q, 1)
        levels = _levels(Q)
        heads = [slice(i * K, (i + 1) * K) for i in range(hb)]
        # A phase at a time over the heads: a head's products wait on one another (eleven in the solve alone).
        to_solve, to_write = [], []
        for lanes in heads:
            g = jnp.where(mine, g_ref[:, lanes], 0.0)                          # (Q, K): a foreign token neither decays
            kb = jnp.where(mine, kb_ref[:, lanes], 0.0)                        # .. nor corrects (beta = 0)
            vb = jnp.where(mine, vb_ref[:, lanes], 0.0)
            k, q = k_ref[:, lanes], q_ref[:, lanes]
            Lm, Am, behind, ahead = _halved(q, k, kb, g, levels)
            G = behind + g[0:1, :]                                             # (Q, K) the running sum, never rises
            sm = s_ref[lanes, :].astype(mm)                                    # (K, V): a head's K rows lie as its K lanes do
            eG = jnp.exp(G)
            to_solve.append((Lm, vb - dot((kb * eG).astype(mm), sm, rounded)))
            to_write.append((Am, dot((q * eG).astype(mm), sm, rounded), ahead, G[Q - 1:Q, :]))
        # (I + L)^-1 = (I - L)(I + L^2)(I + L^4) ..: L^Q = 0.
        powers = [Lm for Lm, _ in to_solve]
        us = [rhs - dot(Lm, rhs, exact) for Lm, rhs in to_solve]
        span = 2
        while span < Q:
            top = span // 8 * 8   # L^span's first span rows are zero: whole registers of them are fed to neither product
            powers = [_under(top, dot(p[top:], p, exact)) for p in powers]
            us = [u + _under(top, dot(p[top:], u, exact)) for p, u in zip(powers, us)]
            span *= 2
        for lanes, (Am, from_state, ahead, end), u in zip(heads, to_write, us):
            um = u.astype(mm)
            out = from_state + dot(Am.astype(mm), um, rounded)
            o_ref[:, lanes] = jnp.where(mine, out, o_ref[:, lanes])
            carry = (k_ref[:, lanes] * jnp.exp(ahead)).astype(mm)              # (Q, K): k e^{G_Q - G}
            grown = jax.lax.dot_general(carry, um, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
                                        **rounded)                              # (K, V)
            s_ref[lanes, :] = jnp.broadcast_to(jnp.exp(end), (K, K)).T * s_ref[lanes, :] + grown

    @pl.when(live & last)
    def _():
        save = pltpu.make_async_copy(s_ref, mine_at(store_out), sem.at[1])
        save.start()
        save.wait()


@functools.partial(jax.jit, static_argnames=("Q", "mm", "interpret"))
def kda_chunk_tpu(store, ordinal, q, k, v, g, beta, meta, *, Q: int, mm=jnp.float32, interpret: bool = False):
    """``kda_chunk`` by the Pallas kernel. The store is updated in place. Q is
    a power of two: a block is halved down to single tokens."""
    if Q & (Q - 1):
        raise ValueError(f"a block of {Q} tokens does not halve down to single tokens")
    T, Hh, K = q.shape
    hb = HEAD_BLOCK if Hh % HEAD_BLOCK == 0 else Hh
    Tp, (q, k, kb, vb, g) = _padded(T, Q, q, k, k * beta[..., None], v * beta[..., None], g)
    flat = lambda a: a.reshape(Tp, Hh * K)
    at = lambda f: (lambda h, j, o, m: f(h, j, m))
    block = pl.BlockSpec((Q, hb * K), at(lambda h, j, m: (m[j * META], h)))
    rows = store.reshape(store.shape[:2] + (Hh * K, K))                        # a head's K rows under one another
    o, rows = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, hb=hb, Q=Q, K=K, mm=mm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(Hh // hb, live_visits(meta)),
            in_specs=[block, block, block, block, block, pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=[block, pl.BlockSpec(memory_space=pltpu.ANY)],
            scratch_shapes=[pltpu.VMEM((hb * K, K), jnp.float32), pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((Tp, Hh * K), jnp.float32), jax.ShapeDtypeStruct(rows.shape, rows.dtype)],
        input_output_aliases={7: 1},   # the store: operand 7 counting the two scalar operands
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="kda_chunk_kernel",
    )(jnp.reshape(ordinal, (1,)).astype(jnp.int32), meta.reshape(-1), flat(q), flat(k), flat(kb), flat(vb), flat(g), rows)
    return o[:T].reshape(T, Hh, K), rows.reshape(store.shape)


def _pairs_alone(q, k, kb, g):
    """L and A of one block, every head at once, every pair formed alone: q, k,
    kb (= beta k), g (Q, Hh, K). Returns (L, A (t, s, Hh); G (Q, Hh, K))."""
    Q = q.shape[0]
    G = jnp.cumsum(g, axis=0)
    pair = jnp.exp(jnp.minimum(G[:, None] - G[None, :], 0.0))                  # (t, s, Hh, K)
    t_of, s_of = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    Lm = jnp.where((s_of < t_of)[..., None], jnp.sum(kb[:, None] * k[None, :] * pair, axis=-1), 0.0)   # (t, s, Hh)
    Am = jnp.where((s_of <= t_of)[..., None], jnp.sum(q[:, None] * k[None, :] * pair, axis=-1), 0.0)
    return Lm, Am, G


def _block(state, q, k, v, g, beta, mm):
    """One block of one row, every head at once (``_pairs_alone``): state (Hh,
    K, V); q, k, v, g (Q, Hh, K); beta (Q, Hh), foreign tokens at g = 0 and
    beta = 0. Returns (o (Q, Hh, V), the state after the block)."""
    es = functools.partial(jnp.einsum, precision=HIGHEST)
    rounded = lambda a: a.astype(mm).astype(jnp.float32)
    Q = q.shape[0]
    kb = k * beta[..., None]
    Lm, Am, G = _pairs_alone(q, k, kb, g)
    eG, sm = jnp.exp(G), rounded(state)
    rhs = v * beta[..., None] - es("thk,hkv->thv", rounded(kb * eG), sm)
    unit = jnp.eye(Q)[..., None] + Lm
    u = jax.vmap(lambda m, r: jax.scipy.linalg.solve_triangular(m, r, lower=True, unit_diagonal=True),
                 in_axes=(2, 1), out_axes=1)(unit, rhs)
    um = rounded(u)
    o = es("thk,hkv->thv", rounded(q * eG), sm) + es("tsh,shv->thv", rounded(Am), um)
    end = G[-1]
    new = jnp.exp(end)[..., None] * state + es("shk,shv->hkv", rounded(k * jnp.exp(jnp.minimum(end[None] - G, 0.0))), um)
    return o, new


def kda_chunk_twin(store, ordinal, q, k, v, g, beta, meta, *, Q: int, mm=jnp.float32):
    """``kda_chunk`` in plain ``jax.numpy``: the same visits, one after
    another, every head at once (``_block``)."""
    T = q.shape[0]
    Tp, (q, k, v, g, beta) = _padded(T, Q, q, k, v, g, beta)
    entry = jax.lax.dynamic_index_in_dim(store, ordinal, 0, keepdims=False)    # (S, Hh, K, V)

    def visit(j, carry):
        entry, state, o = carry
        block, slot, lo, hi, first, last, fresh, _ = (meta[j, w] for w in range(META))
        live = hi > lo
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, block * Q, Q, 0)
        mine = (jnp.arange(Q) >= lo) & (jnp.arange(Q) < hi)
        state = jnp.where((first == 1) & (fresh == 0), entry[slot], jnp.where(first == 1, 0.0, state))
        ob, new = _block(state, take(q), take(k), take(v), jnp.where(mine[:, None, None], take(g), 0.0),
                         jnp.where(mine[:, None], take(beta), 0.0), mm)
        state = jnp.where(live, new, state)
        o = jax.lax.dynamic_update_slice_in_dim(o, jnp.where((mine & live)[:, None, None], ob, take(o)), block * Q, 0)
        entry = jnp.where(live & (last == 1), entry.at[slot].set(state), entry)
        return entry, state, o

    entry, _, o = jax.lax.fori_loop(0, meta.shape[0], visit,
                                    (entry, jnp.zeros(entry.shape[1:], jnp.float32), jnp.zeros_like(v)))
    return o[:T], jax.lax.dynamic_update_index_in_dim(store, entry, ordinal, 0)


def kda_chunk(store, ordinal, q, k, v, g, beta, meta, *, Q: int, mm=jnp.float32, kernel: bool | None = None):
    """The rows of more than one token of a mixed step, blocked. store (state
    layers, slots, Hh, K, V) float32, ``ordinal`` the layer's entry; q, k, g
    (T, Hh, K) and v (T, Hh, V) the packed tokens' operands (q, k normalised by
    the caller; g <= 0 the log of the decay), beta (T, Hh); ``meta`` =
    ``ops/ssd.block_plan(...)[0]`` at the same Q. Returns (o (T, Hh, V) float32,
    defined at the tokens of rows with more than one token and arbitrary
    elsewhere; the store with those rows' slots at their last state). ``mm``:
    the type the operands of the products with the state and with U are
    rounded to; sums, decays, the solve and the state are float32 either way.
    ``kernel`` None: the Pallas kernel on a TPU, the twin elsewhere; tests
    force the kernel, interpreted off the TPU."""
    with jax.named_scope("ssm.scan"):
        if kernel is None:
            kernel = on_tpu()
        if kernel:
            return kda_chunk_tpu(store, ordinal, q, k, v, g, beta, meta, Q=Q, mm=mm, interpret=not on_tpu())
        return kda_chunk_twin(store, ordinal, q, k, v, g, beta, meta, Q=Q, mm=mm)


def recurrence(state, q, k, v, g, beta):
    """The definition, a token at a time, for ONE sequence: state (Hh, K, V);
    q, k, g (T, Hh, K), v (T, Hh, V), beta (T, Hh). Returns (o (T, Hh, V), the
    last state). The tests' oracle."""
    def step(S, tok):
        qt, kt, vt, gt, bt = tok
        S = jnp.exp(gt)[..., None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt, precision=HIGHEST))
        S = S + kt[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HIGHEST)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state
