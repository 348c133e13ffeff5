"""EVA attention's cache (ISSUE 61): a window of exact rows folded into summaries.

EVA ("Efficient Attention via Control Variates", arXiv:2302.04542, in the
deterministic form EvaByte serves) lets a query see the keys of its own window
of ``window`` tokens exactly, and every earlier window as one summary per
``chunk`` tokens: the chunk's keys pooled under a softmax of ``k . mu_h`` and
its values under a softmax of ``k . phi_h``, two learned vectors a head. One
softmax runs over both sets.

The page pool holds both in ONE row space, so the paged kernels read a slot as
they read any other (``cached_rows``): summaries first, a page a window
(``window / chunk`` is the page size), the current window's exact rows behind
them. When a row writes the first token of a window, the window before it is
FOLDED: its ``window / page`` pages of exact rows are read, pooled in float32,
and its first page is rewritten with the summaries. The pages behind it are the
new window's (their old rows lie past the row's cached length until they are
overwritten). Plain ``jax.numpy``, the same on the chip and on the CPU: there is
no twin, and no kernel while a trace does not ask for one.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


def cached_rows(n, window: int, chunk: int):
    """Rows of the pool a context of ``n`` tokens holds: a summary a chunk of
    every window behind the last token's, and that window's tokens. Integer
    arithmetic alone, so the host (ints, numpy) and the step programs (traced
    arrays) call the same function. The row of token t is ``cached_rows(t + 1) - 1``."""
    w = (n - (n > 0)) // window   # (an empty context holds none)
    return n - w * (window - window // chunk)


@dataclass(frozen=True)
class FoldedRows:
    """``cached_rows`` at one window and chunk, as a family's record hands it to the
    serving path (``Family.cached_rows``): called with a context, it gives its rows."""
    window: int   # tokens: the rows fall at every multiple, and no prompt chunk straddles one
    chunk: int

    def __call__(self, n):
        return cached_rows(n, self.window, self.chunk)

    @property
    def page_size(self) -> int:
        """A window's summaries fill one page: the one page size the pool is laid out for."""
        return self.window // self.chunk


def pool_chunks(k, v, mu, phi, chunk: int):
    """The summaries of whole chunks. ``k``, ``v`` (N, heads, D), rotated keys as
    the pool holds them, N a multiple of ``chunk``; ``mu``, ``phi`` (heads, D).
    Returns (k~, v~), (N / chunk, heads, D) each in float32: the softmaxes and
    the sums run in it. Products and sums are elementwise, so on a TPU nothing
    passes through the matrix unit's bf16."""
    N, H, D = k.shape
    kf = k.astype(jnp.float32).reshape(N // chunk, chunk, H, D)
    vf = v.astype(jnp.float32).reshape(N // chunk, chunk, H, D)
    s = D ** -0.5
    a = jax.nn.softmax(s * jnp.sum(kf * mu.astype(jnp.float32), axis=-1), axis=1)    # (chunks, chunk, heads)
    b = jax.nn.softmax(s * jnp.sum(kf * phi.astype(jnp.float32), axis=-1), axis=1)
    return jnp.sum(a[..., None] * kf, axis=1), jnp.sum(b[..., None] * vf, axis=1)


def fold(cache: dict, table, first_pos, live, mu, phi, *, window: int, chunk: int):
    """Fold the window behind every row that starts a window in this step.

    ``cache`` {"k", "v"}: (L, P, page, heads * D); ``table`` (R, pages a slot);
    ``first_pos`` (R,) the position of the first token a row writes in this
    step and ``live`` (R,) whether it writes any; ``mu``, ``phi`` (L, heads, D).
    Decided here, on the device, from the positions: a row rolls where
    ``first_pos`` is a positive multiple of ``window``. It must run BEFORE the
    step's own writes (the new window's first token lands in the page behind
    the one rewritten). Returns (the cache, the number of rows that rolled).

    The rows that roll are walked by a loop whose trip count is their number: a
    step without a roll runs no iteration, and the pools are the loop's carry,
    updated in place. (Under ``lax.cond`` the chip's compiler copies a pool
    that one branch updates and the other hands back: PERF.md section 6, PR 43.)"""
    L, P, page, HD = cache["k"].shape
    heads = mu.shape[1]
    per_window = window // page
    assert window % page == 0 and window // chunk == page, (
        f"a window's summaries fill one page: window {window} / chunk {chunk} != page size {page}")
    roll = live & (first_pos > 0) & (first_pos % window == 0)
    order = jnp.argsort(~roll, stable=True)          # the rows that roll first
    folded_at = first_pos // window - 1              # where in a slot's page list the folded window starts

    def one_row(i, pools):
        r = order[i]
        pages = jax.lax.dynamic_slice(jnp.take(table, r, axis=0), (folded_at[r],), (per_window,))

        def one_layer(layer, pools):
            ck, cv = pools
            ids = pages + layer * P
            # A page at a time, sliced where it lies: a gather of the pages by index made
            # the chip's compiler lay half the pool out anew (1.6 GB twice at 6.5B).
            rows = lambda pool: jnp.concatenate(
                [jax.lax.dynamic_slice(pool, (ids[i] * page, 0), (page, HD)) for i in range(per_window)]
            ).reshape(window, heads, HD // heads)
            ks, vs = pool_chunks(rows(ck), rows(cv), jnp.take(mu, layer, axis=0), jnp.take(phi, layer, axis=0), chunk)
            at = (ids[0] * page, 0)
            return (jax.lax.dynamic_update_slice(ck, ks.reshape(page, HD).astype(ck.dtype), at),
                    jax.lax.dynamic_update_slice(cv, vs.reshape(page, HD).astype(cv.dtype), at))

        return jax.lax.fori_loop(0, L, one_layer, pools)

    rolls = jnp.sum(roll.astype(jnp.int32))
    with jax.named_scope("kv.write"):
        ck, cv = jax.lax.fori_loop(
            0, rolls, one_row, (cache["k"].reshape(L * P * page, HD), cache["v"].reshape(L * P * page, HD)))
    return {**cache, "k": ck.reshape(L, P, page, HD), "v": cv.reshape(L, P, page, HD)}, rolls
