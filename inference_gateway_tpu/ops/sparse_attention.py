"""Learned sparse attention over the paged cache (ISSUE 36): the lightning
indexer of DeepSeek-Sparse-Attention in front of grouped-query attention.

Every cached token holds, beside its keys and values, one small indexer
key (``Di`` wide, under the same page ids; a page of them lies transposed,
(Di, page_size): the tokens in the lanes, so a 64-wide key wastes no half
lane row and the scores' product needs no relayout). A query scores every
token it can see,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        float32 sums

keeps the ``topk`` largest (equal scores: the lower position first, the
rule of ``lax.top_k``), and attends over those tokens alone. A query that
sees ``topk`` tokens or fewer keeps them all: plain causal attention.

Plain XLA but for a one-query row's read. Three ways to read the chosen
tokens, by the shape of the row and of the page table, and the counters say
which was taken:

- a row of ONE query (a decode row, in the decode chunk and in a mixed
  step), where a row of the page table holds at most ``WALK_UNDER`` x
  ``topk`` tokens (ISSUE 45): the chosen set is a MASK over the row (the
  exact ``topk``-th largest score by ``topk_mask``: no sort), and the
  paged decode kernel walks the row's pages whole under it
  (``ops/paged_attention.py``, its ``mask`` operand). The attention reads
  every page the row holds: at such contexts (a few ``topk``) every page
  holds chosen tokens, and the chip walks a page at its bytes' time where
  a token's gather costs nine times that and the sort before it grows
  with the table (``one_query_read``).
- a row of ONE query on a wider table: ``lax.top_k`` over the row's
  scores, then a token-granular gather of the chosen keys and values
  through the page table into a dense attention over ``topk`` tokens. The
  attention reads ``topk`` tokens, whatever the context holds.
- a row of several queries (a prompt chunk): each query has a set of its
  own, so the row is walked in tiles of ``TILE`` queries; a tile's scores
  go into a (TILE, S) buffer a block of keys at a time, each query's
  ``topk``-th largest score is found exactly (``kth_largest``: a bisection
  over the order-preserving integer image of float32, 32 counting passes)
  and becomes a mask (ties cut by position), and a flash-style loop over
  the same blocks of keys attends under the mask. The attention reads
  every block up to the tile's last position (``sparse_ragged``): a
  prompt chunk's reads are not sparse, its numbers are the selection's.

Both loops run for as many tiles and blocks as the step's rows need
(dynamic trip counts), so a short context pays for a short context.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from inference_gateway_tpu.ops.paged_attention import paged_attention

NEG = -1e30
TILE = 128          # queries a tile of a prompt chunk
BLOCK_TOKENS = 1024  # keys a block of either loop of a tile, rounded to whole pages
# A one-query row's read is the masked walk of its pages where a row of the
# page table holds at most this many times ``topk`` tokens, the gather of the
# chosen tokens above. On a v5e at Keye-VL-2.0's layout (PERF.md section 5, PR
# 45) 12 FULL rows of 12 x topk walk in 0.89 ms where the sort and the two
# gathers take 0.98, rows of 16 x topk filled to 12 x 0.91 against 1.08 and
# filled whole 1.17: the walk's time follows the context, the gather's the
# table, so the table at which a full row breaks even is the latest safe one.
WALK_UNDER = 12


def one_query_read(topk: int, row_tokens: int) -> str:
    """How a one-query row reads its chosen tokens on a page table whose row
    holds ``row_tokens``: static a program, so a /debug/status entry."""
    return "walk" if row_tokens <= WALK_UNDER * topk else "gather"


def one_query_tokens_read(topk: int, row_tokens: int, page_size: int, lengths: jnp.ndarray) -> jnp.ndarray:
    """Tokens whose keys and values a one-query row's attention reads, for
    each of ``lengths`` (a live row's context): the pages it holds on the
    walk, ``topk`` on the gather."""
    if one_query_read(topk, row_tokens) == "walk":
        return -(-lengths // page_size) * page_size
    return jnp.full_like(lengths, min(topk, row_tokens))


def order_key(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32, order-preserving (-inf lowest, +inf highest)."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """keys (N, S) uint32 -> (N,) the k-th largest of each row, exactly:
    the largest t with count(keys >= t) >= k, built bit by bit from the
    top (k <= S)."""
    def body(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        ok = jnp.sum(keys >= cand[:, None], axis=1) >= k
        return jnp.where(ok, cand, t)

    return jax.lax.fori_loop(0, 32, body, jnp.zeros((keys.shape[0],), jnp.uint32))


def topk_mask(scores: jnp.ndarray, visible: jnp.ndarray, k: int) -> jnp.ndarray:
    """scores (N, S) float32 with -inf where not ``visible`` -> (N, S) bool:
    each row's k largest visible scores (all of them where fewer are
    visible), equal scores taken from the lower position, as
    ``lax.top_k`` orders them."""
    keys = order_key(scores)
    t = kth_largest(keys, k)[:, None]
    above = keys > t
    equal = keys == t
    room = k - jnp.sum(above, axis=1, keepdims=True)
    # Scores are float32 sums: a tie at the cut is rare, and only then is a
    # row's prefix count worth its pass (a cumsum over [TILE, S] was 5% of a
    # v5e's busy time in the long-context cell: PERF.md, PR 36). A row that
    # sees fewer than k cuts at -inf: its equals are invisible either way.
    tied = jnp.any((jnp.sum(equal, axis=1, keepdims=True) > room) & (t > order_key(jnp.float32(-jnp.inf))))
    keep = jax.lax.cond(tied, lambda: equal & (jnp.cumsum(equal, axis=1) <= room), lambda: equal)
    return (above | keep) & visible


def index_scores(qi: jnp.ndarray, wi: jnp.ndarray, ki: jnp.ndarray) -> jnp.ndarray:
    """qi (..., N, Hi, Di), wi (..., N, Hi) float32, ki (..., pages, Di,
    page_size) the indexer's keys a page -> (..., N, pages * page_size)
    float32."""
    s = jnp.einsum("...nhd,...pds->...nhps", qi, ki.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("...nh,...nhps->...nps", wi, jax.nn.relu(s))
    return out.reshape(out.shape[:-2] + (-1,))


def sparse_decode(q, qi, wi, flat_k, flat_v, ki_pages, table, lengths, *, topk: int,
                  num_kv_heads: int, page_size: int):
    """One query a row. q (B, Hq, D); qi (B, Hi, Di); wi (B, Hi) float32;
    flat_k, flat_v (tokens, width) the whole pool, a token a row; ki_pages
    (pages, Di, page_size) the indexer's keys by page; table (B, pages)
    page ids into them (a layer's offset added); lengths (B,) tokens visible,
    the query's own included (0: an idle row, whose output means nothing).
    Returns (B, Hq, D). The read is ``one_query_read``'s for the table."""
    B, Hq, D = q.shape
    S = table.shape[1] * page_size
    k = min(topk, S)
    with jax.named_scope("dsa.index"):
        scores = index_scores(qi[:, None], wi[:, None], ki_pages[table])[:, 0]    # (B, S), whole pages gathered
        visible = jnp.arange(S)[None, :] < lengths[:, None]
        scores = jnp.where(visible, scores, -jnp.inf)
    if one_query_read(topk, S) == "walk":
        with jax.named_scope("dsa.select"):
            mask = topk_mask(scores, visible, k)   # fewer visible than k: all of them
        with jax.named_scope("dsa.attend"):
            pages_k, pages_v = (flat.reshape(-1, page_size, flat.shape[-1]) for flat in (flat_k, flat_v))
            return paged_attention(q, pages_k, pages_v, table, lengths, num_kv_heads, mask=mask,
                                   name="dsa_paged_attn_kernel")
    with jax.named_scope("dsa.select"):
        _, idx = jax.lax.top_k(scores, k)                                         # (B, k)
        chosen = idx < lengths[:, None]        # fewer visible than k: the tail is padding
    with jax.named_scope("dsa.attend"):
        # The chosen tokens' rows of the pool. The page of each is looked up by
        # comparison against the row's few page slots (an elementwise gather of
        # B x k ids from the table cost 0.24 ms a layer on a v5e: PERF.md, PR 36).
        slot = (idx // page_size)[:, :, None] == jnp.arange(table.shape[1])[None, None, :]
        rows = jnp.sum(jnp.where(slot, table[:, None, :], 0), axis=-1) * page_size + idx % page_size
        kk = flat_k[rows].reshape(B, k, num_kv_heads, D).astype(q.dtype)
        vv = flat_v[rows].reshape(B, k, num_kv_heads, D).astype(q.dtype)
        G = Hq // num_kv_heads
        qg = q.reshape(B, num_kv_heads, G, D)
        s = jnp.einsum("bhgd,bshd->bhgs", qg, kk, preferred_element_type=jnp.float32) * D ** -0.5
        s = jnp.where(chosen[:, None, None, :], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgs,bshd->bhgd", p.astype(vv.dtype), vv, preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, D).astype(q.dtype)


def block_tokens(page_size: int, row_tokens: int) -> int:
    """Keys a block of the tile loops: about BLOCK_TOKENS, whole pages, and
    no more than a row of the page table holds."""
    return min(max(BLOCK_TOKENS // page_size, 1), -(-row_tokens // page_size)) * page_size


def sparse_ragged(q, qi, wi, positions, flat_k, flat_v, ki_pages, table, q_starts, q_lens,
                  kv_lens, *, topk: int, num_kv_heads: int, page_size: int):
    """A mixed step's packed queries. q (T, Hq, D); qi (T, Hi, Di); wi
    (T, Hi) float32; positions (T,); table (R, pages); q_starts, q_lens,
    kv_lens (R,) as the ragged kernel takes them. Returns ((T, Hq, D),
    tokens walked (T,): for each packed query the tokens whose keys and
    values its attention read)."""
    T, Hq, D = q.shape
    R = table.shape[0]
    Hkv, G = num_kv_heads, Hq // num_kv_heads
    KB = block_tokens(page_size, table.shape[1] * page_size)
    ppb = KB // page_size
    # Rows of one query: as a decode step's (and on its table: one width decides
    # both programs' read), all at once.
    single = q_lens == 1
    at = jnp.clip(q_starts, 0, T - 1)
    row_tokens = table.shape[1] * page_size
    narrow = sparse_decode(q[at], qi[at], wi[at], flat_k, flat_v, ki_pages, table,
                           jnp.where(single, kv_lens, 0), topk=topk,
                           num_kv_heads=num_kv_heads, page_size=page_size)

    # Rows of several: tiles of TILE queries, a row after another.
    pages = -(-table.shape[1] // ppb) * ppb
    table = jnp.pad(table, ((0, 0), (0, pages - table.shape[1])))   # whole blocks; the pad is never visible
    S = pages * page_size
    tiles = jnp.where(q_lens > 1, -(-q_lens // TILE), 0)
    ends = jnp.cumsum(tiles)
    pad = lambda a: jnp.pad(a, ((0, TILE),) + ((0, 0),) * (a.ndim - 1))
    qp, qip, wip, posp = pad(q), pad(qi), pad(wi), pad(positions)
    span = jnp.arange(S)
    pages_k, pages_v = (flat.reshape(-1, page_size, flat.shape[-1]) for flat in (flat_k, flat_v))   # the pool by page

    def tile_body(i, carry):
        out, walked = carry
        r = jnp.searchsorted(ends, i, side="right")
        j = i - (ends[r] - tiles[r])
        q0 = q_starts[r] + j * TILE
        live = jnp.arange(TILE) < q_lens[r] - j * TILE
        qt = jax.lax.dynamic_slice_in_dim(qp, q0, TILE)
        qit = jax.lax.dynamic_slice_in_dim(qip, q0, TILE)
        wit = jax.lax.dynamic_slice_in_dim(wip, q0, TILE)
        pos = jnp.where(live, jax.lax.dynamic_slice_in_dim(posp, q0, TILE), -1)
        ctx = jnp.max(pos) + 1
        nblk = -(-ctx // KB)
        row_pages = table[r]
        visible = span[None, :] <= pos[:, None]

        def block(pool, c):
            """Block c of the row's keys: its ppb pages of ``pool`` (pages, ...), gathered whole."""
            return pool[jax.lax.dynamic_slice_in_dim(row_pages, c * ppb, ppb)]

        with jax.named_scope("dsa.index"):
            def index_block(c, buf):
                blk = index_scores(qit, wit, block(ki_pages, c))                   # (TILE, KB)
                return jax.lax.dynamic_update_slice_in_dim(buf, blk, c * KB, axis=1)

            scores = jax.lax.fori_loop(0, nblk, index_block,
                                       jnp.full((TILE, S), -jnp.inf, jnp.float32))
            scores = jnp.where(visible, scores, -jnp.inf)
        with jax.named_scope("dsa.select"):
            mask = jax.lax.cond(ctx > topk, lambda: topk_mask(scores, visible, topk),
                                lambda: visible)
        with jax.named_scope("dsa.attend"):
            qg = qt.reshape(TILE, Hkv, G, D)

            def attend_block(c, st):
                m, l, acc = st
                kk = block(pages_k, c).reshape(KB, Hkv, D).astype(q.dtype)
                vv = block(pages_v, c).reshape(KB, Hkv, D).astype(q.dtype)
                mk = jax.lax.dynamic_slice_in_dim(mask, c * KB, KB, axis=1)
                s = jnp.einsum("qhgd,shd->hgqs", qg, kk, preferred_element_type=jnp.float32) * D ** -0.5
                s = jnp.where(mk[None, None], s, NEG)
                mn = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.where(mk[None, None], jnp.exp(s - mn[..., None]), 0.0)
                a = jnp.exp(m - mn)
                l = l * a + jnp.sum(p, axis=-1)
                acc = acc * a[..., None] + jnp.einsum(
                    "hgqs,shd->hgqd", p.astype(vv.dtype), vv, preferred_element_type=jnp.float32)
                return mn, l, acc

            m0 = jnp.full((Hkv, G, TILE), NEG, jnp.float32)
            _, l, acc = jax.lax.fori_loop(
                0, nblk, attend_block,
                (m0, jnp.zeros_like(m0), jnp.zeros((Hkv, G, TILE, D), jnp.float32)))
            o = (acc / jnp.maximum(l, 1e-30)[..., None]).transpose(2, 0, 1, 3).reshape(TILE, Hq, D)
        old = jax.lax.dynamic_slice_in_dim(out, q0, TILE)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(live[:, None, None], o.astype(out.dtype), old), q0, axis=0)
        seen = jax.lax.dynamic_slice_in_dim(walked, q0, TILE)
        walked = jax.lax.dynamic_update_slice_in_dim(
            walked, jnp.where(live, nblk * KB, seen), q0, axis=0)
        return out, walked

    out, walked = jax.lax.fori_loop(
        0, ends[-1], tile_body,
        (jnp.zeros((T + TILE, Hq, D), q.dtype), jnp.zeros((T + TILE,), jnp.int32)))
    drop = jnp.where(single, q_starts, T + TILE)
    out = out.at[drop].set(narrow, mode="drop")
    walked = walked.at[drop].set(one_query_tokens_read(topk, row_tokens, page_size, kv_lens), mode="drop")
    return out[:T], walked[:T]
