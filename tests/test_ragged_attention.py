"""Ragged paged attention: kernel ≡ pure-JAX reference (ISSUE 12).

Tier-1 CPU coverage for the mixed-batch ragged kernel: every case runs
the Pallas kernel in ``interpret=True`` mode against the pure-JAX ragged
reference — mixed prefill+decode batches, ragged lengths including
1-token decode rows, page-boundary-straddling chunks, inactive rows,
sliding windows, and the non-128-aligned folded axes that used to force
the gather path. Kernel correctness is testable without a TPU window.

ISSUE 34: the kernel walks a row in wide tiles; the cases below put rows on
both sides of the wide width and of the 1-, 8- and 32-query marks an earlier
tiling had, in every layout.

ISSUE 44: the launch hands the one-query rows of the packed prefix to the
decode kernel (several pages a visit) and joins the two outputs; a wide tile's
visit is several pages under one flash update, masked only on the tile's
diagonal and at its window's near edge. The cases below walk one-query rows
of every length against the pages a visit, put a chunk's tail of one query
inside and outside the prefix, and lay a window's near edge mid-page, on a
page boundary, inside the chunk and on the diagonal page, at 1, 2 and 3 pages
a visit, the window static and an operand.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.ops.paged_attention import (
    GLOBAL_WINDOW,
    decode_walk_plan,
    paged_attention_jax,
    paged_attention_tpu,
    ragged_paged_attention,
    ragged_paged_attention_jax,
    ragged_paged_attention_tpu,
    ragged_tile_plan,
    ragged_walk_pages,
)

# The tiling every case below is laid out against (page size 16 or 128,
# G up to 7: the score tile never cuts the wide width).
PLAN = ragged_tile_plan(8, 2, 64, 16, 10)
WIDE, WIDE_FROM = PLAN["wide_tile"], PLAN["wide_from"]


def _mixed_case(rng, Hq, Hkv, D, ps, P, mp, q_lens, kv_lens, dtype=np.float32):
    R = len(q_lens)
    q_lens = np.asarray(q_lens, np.int32)
    kv_lens = np.asarray(kv_lens, np.int32)
    q_starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    T = int(q_lens.sum())
    q = jnp.asarray(rng.normal(size=(max(T, 1), Hq, D)).astype(dtype))
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(dtype))
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(dtype))
    pt = jnp.asarray(rng.permutation(P)[: R * mp].reshape(R, mp).astype(np.int32))
    return q, k, v, pt, jnp.asarray(q_starts), jnp.asarray(q_lens), jnp.asarray(kv_lens)


def _assert_kernel_matches(case, Hkv, window=None, atol=1e-5):
    q, k, v, pt, qs, ql, kl = case
    ref = ragged_paged_attention_jax(q, k, v, pt, qs, ql, kl, Hkv, window=window)
    out = ragged_paged_attention_tpu(q, k, v, pt, qs, ql, kl, Hkv,
                                     interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=atol)


# The layout matrix: (Hq, Hkv, D) — aligned, misaligned folded axis
# (Hkv·D = 192), odd head_dim (folded 192 via D=48), single-kv-head.
LAYOUTS = [
    pytest.param(8, 4, 64, id="aligned_256"),
    pytest.param(6, 3, 64, id="misaligned_192"),
    pytest.param(8, 4, 48, id="misaligned_head_48"),
    pytest.param(4, 1, 64, id="mqa_64"),
    # Qwen2.5 / SmallThinker: G = 7 over a 512-lane folded axis.
    pytest.param(28, 4, 128, id="g7_512"),
]

# (q_lens, kv_lens) of one launch. "classic": decode rows (q_len 1), a
# page-straddling chunk (37 queries: a wide row), an inactive row, a fresh
# prefill, a decode row at 1. "alternating": narrow and wide rows turn
# about, so each wide tile's window overhangs a narrow neighbour's lanes
# and the other way round (the preserve-and-defer writes).
BATCHES = [
    pytest.param([1, 37, 0, 24, 1], [45, 60, 0, 24, 1], id="classic"),
    pytest.param([1, 140, 5, 32, 1, WIDE + 2, 31, 0, 3],
                 [77, 150, 5, 90, 1, WIDE + 9, 40, 0, 19], id="alternating"),
]


@pytest.mark.parametrize("q_lens,kv_lens", BATCHES)
@pytest.mark.parametrize("Hq,Hkv,D", LAYOUTS)
def test_ragged_kernel_mixed_batch_matches_reference(Hq, Hkv, D, q_lens, kv_lens):
    """Decode rows, prefill chunks on both tiles, a fresh full prefill and
    inactive rows in ONE launch — including the folded-axis layouts that
    previously forced the gather path."""
    rng = np.random.default_rng(0)
    ps, mp = 16, 10
    P = mp * len(q_lens)
    case = _mixed_case(rng, Hq, Hkv, D, ps, P, mp, q_lens, kv_lens)
    _assert_kernel_matches(case, Hkv)


@pytest.mark.parametrize("Hq,Hkv,D", LAYOUTS)
def test_ragged_kernel_bf16_inputs_match_twin(Hq, Hkv, D):
    """bf16 queries and pages, as on the chip: the operands reach both
    products as stored and the probabilities in V's dtype, the twin's own
    arithmetic. What is left is the order of the sums and where the
    probabilities are rounded (before the kernel's normalisation, after
    the twin's): within two bf16 steps of an output of magnitude 1-2."""
    rng = np.random.default_rng(6)
    ps, mp = 16, 10
    q_lens = [1, 140, 5, 32, 0, 1]
    kv_lens = [77, 150, 5, 90, 0, 1]
    q, k, v, *rest = _mixed_case(rng, Hq, Hkv, D, ps, mp * len(q_lens), mp, q_lens, kv_lens)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = ragged_paged_attention_jax(q, k, v, *rest, Hkv)
    out = ragged_paged_attention_tpu(q, k, v, *rest, Hkv, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=0, atol=2 ** -6)


@pytest.mark.parametrize("Hq,Hkv,D", LAYOUTS)
def test_ragged_kernel_decode_only_matches_classic_reference(Hq, Hkv, D):
    """All-decode ragged batches reduce to the classic paged decode
    contract: same numbers as paged_attention_jax row for row."""
    rng = np.random.default_rng(1)
    ps, P, mp = 16, 32, 6
    lengths = [33, 1, 16, 90]
    q_lens = [1] * len(lengths)
    case = _mixed_case(rng, Hq, Hkv, D, ps, P, mp, q_lens, lengths)
    q, k, v, pt, qs, ql, kl = case
    _assert_kernel_matches(case, Hkv)
    classic = paged_attention_jax(q, k, v, pt, kl, Hkv)
    ragged = ragged_paged_attention_jax(q, k, v, pt, qs, ql, kl, Hkv)
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(classic),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("Hq,Hkv,D,ps,mp,q_lens,kv_lens,W,dead_pages", [
    # Row 0 (decode at kv 90, window 24) cannot see tokens < 66: pages 0..3.
    pytest.param(8, 4, 64, 16, 8, [1, 20, 1], [90, 70, 9], 24, 4, id="mixed_w24"),
    # A wide row under a window shorter than itself: 100 queries, 24 keys each.
    pytest.param(8, 4, 64, 16, 12, [1, 100, 1], [90, 180, 9], 24, 4, id="wide_row_w24"),
    # Phi-4-mini-flash's shape: a 512-token chunk at context 1,500 under the
    # 512-token window (40 zero-padded differential heads over 10 KV heads of
    # 128); row 0's first query sits at 988 and sees keys > 476: pages 0..2.
    pytest.param(40, 10, 128, 128, 12, [512, 1], [1500, 700], 512, 3,
                 id="phi4_chunk512_w512"),
])
def test_ragged_kernel_window_matches_reference(Hq, Hkv, D, ps, mp, q_lens, kv_lens, W,
                                                dead_pages):
    """Sliding window over a mixed batch: kernel ≡ reference, and keys
    before the window cannot influence the output."""
    rng = np.random.default_rng(2)
    case = _mixed_case(rng, Hq, Hkv, D, ps, mp * len(q_lens), mp, q_lens, kv_lens)
    _assert_kernel_matches(case, Hkv, window=W)
    q, k, v, pt, qs, ql, kl = case
    ref = ragged_paged_attention_jax(q, k, v, pt, qs, ql, kl, Hkv, window=W)
    # Poison the pages that hold only tokens before row 0's window: its
    # output must not move.
    k_bad, v_bad = k, v
    for p in np.asarray(pt)[0][:dead_pages]:
        k_bad = k_bad.at[int(p)].set(1e3)
        v_bad = v_bad.at[int(p)].set(1e3)
    out_bad = ragged_paged_attention_tpu(q, k_bad, v_bad, pt, qs, ql, kl, Hkv,
                                         interpret=True, window=W)
    n0 = q_lens[0]
    np.testing.assert_allclose(np.asarray(out_bad)[:n0], np.asarray(ref)[:n0],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q_lens,kv_lens", [
    # ON page and narrow-tile boundaries (16, 32) and one past them.
    pytest.param([16, 17, 32, 33, 1], [16, 17, 32, 33, 128], id="page_and_narrow_tile"),
    # At and over the threshold: the single-query tile, the first two rows to
    # take the wide one.
    pytest.param([WIDE_FROM - 1, WIDE_FROM, WIDE_FROM + 1], [40, 41, 42],
                 id="wide_from_edges"),
    pytest.param([31, 32, 33], [40, 41, 42], id="around_32"),
    # One under, at and over the wide width: one full tile, one and a query.
    pytest.param([WIDE - 1, WIDE, WIDE + 1], [WIDE + 3, WIDE, WIDE + 30],
                 id="wide_tile_edges"),
    # A whole prompt chunk: four wide tiles, fresh and deep in a prompt.
    pytest.param([512], [512], id="chunk_512_fresh"),
    pytest.param([512, 1], [1000, 300], id="chunk_512_at_1000"),
    # Wide tiles that start mid-page (the row's first query sits at 37, 5
    # into its third page) and cross eight page boundaries each.
    pytest.param([200, 1, 130], [237, 20, 141], id="wide_tile_mid_page"),
])
def test_ragged_kernel_page_boundary_and_qblock_edges(q_lens, kv_lens):
    """Lengths that land exactly ON page and tile boundaries and one past
    them, for both tiles: the masks, not luck, bound the walk."""
    rng = np.random.default_rng(3)
    Hq, Hkv, D, ps = 8, 4, 64, 16
    mp = -(-max(kv_lens) // ps)
    case = _mixed_case(rng, Hq, Hkv, D, ps, mp * len(q_lens), mp, q_lens, kv_lens)
    _assert_kernel_matches(case, Hkv)


@pytest.mark.parametrize("q_lens,kv_lens,T", [
    pytest.param([1, 5], [9, 5], 16, id="narrow_rows"),  # 10 trailing positions belong to nobody
    # A wide row whose one tile overhangs the 23 unowned positions and the slack.
    pytest.param([1, 40], [9, 44], 64, id="wide_row"),
])
def test_ragged_kernel_uncovered_tail_is_zero(q_lens, kv_lens, T):
    """Packed positions not covered by any row come back as zeros from
    both implementations (the engine's padded tail feeds later matmuls)."""
    rng = np.random.default_rng(4)
    Hq, Hkv, D, ps, P, mp = 8, 4, 64, 16, 32, 4
    covered = sum(q_lens)
    q_lens = np.asarray(q_lens, np.int32)
    kv_lens = np.asarray(kv_lens, np.int32)
    q_starts = np.asarray([0, 1], np.int32)
    q = jnp.asarray(rng.normal(size=(T, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    pt = jnp.asarray(rng.permutation(P)[: 2 * mp].reshape(2, mp).astype(np.int32))
    args = (q, k, v, pt, jnp.asarray(q_starts), jnp.asarray(q_lens),
            jnp.asarray(kv_lens))
    ref = ragged_paged_attention_jax(*args, 4)
    out = ragged_paged_attention_tpu(*args, 4, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(ref)[covered:] == 0)
    assert np.all(np.asarray(out)[covered:] == 0)


def test_ragged_tile_plan_follows_the_layout():
    """The wide width is 128 queries where one page's float32 score tile
    (tile·G x page_size) allows it, a multiple of 8 below that; a visit is
    as many pages as RAGGED_VISIT_BYTES holds of buffers, scores and
    probabilities, a row's pages at the most; one-query rows take the
    decode kernel at its own pages a visit."""
    plan = ragged_tile_plan(32, 8, 128, 128, 16)
    assert (plan["wide_tile"], plan["wide_from"], plan["rows_per_pass"]) == (128, 2, 512)
    assert plan["one_query_rows"] == "decode_kernel"
    assert plan["one_query_pages_per_visit"] == decode_walk_plan(32, 8, 128, 128, 16)["pages_per_visit"]
    assert ragged_tile_plan(28, 4, 128, 128, 16)["rows_per_pass"] == 896
    wide_mqa = ragged_tile_plan(32, 1, 128, 128, 16)
    assert wide_mqa["wide_tile"] == 32 and wide_mqa["rows_per_pass"] == 1024
    assert ragged_tile_plan(28, 4, 128, 256, 16)["wide_tile"] == 72
    # More pages a visit where a page is small (4 KV heads against 10), an eighth of a row's at the most.
    small, large = ragged_tile_plan(32, 4, 128, 128, 100), ragged_tile_plan(40, 10, 128, 128, 100)
    assert small["wide_pages_per_visit"] > large["wide_pages_per_visit"] >= 1
    assert ragged_tile_plan(32, 4, 128, 128, 16)["wide_pages_per_visit"] == 2
    assert PLAN["wide_pages_per_visit"] == 1 and PLAN["wide_from"] <= PLAN["wide_tile"]


# One under, at and one over every width a tiling of this kernel has had (1,
# 8, 32, 128) and a whole 512-token chunk, each followed by an inactive row.
BOUNDARY_LENS = [1, 2, 7, 8, 9, 31, 32, 33, 127, 128, 129, 512]


@pytest.mark.parametrize("Hq,Hkv,D,ps,window", [
    pytest.param(8, 2, 64, 16, None, id="g4"),
    pytest.param(14, 2, 64, 16, None, id="g7"),
    # Hkv·D = 192 is no multiple of 128, and the window cuts inside every wide tile.
    pytest.param(6, 3, 64, 16, 24, id="misaligned_192_w24"),
    # The hybrid's layout: 40 zero-padded heads over 10 KV heads of 128, its
    # 512-token window cutting the 512-token chunk's tiles.
    pytest.param(40, 10, 128, 128, 512, id="phi4_w512"),
])
def test_ragged_kernel_matches_twin_across_the_tile_boundaries(Hq, Hkv, D, ps, window):
    """ONE launch with a row at every boundary, fresh and deep in a context
    turn about, inactive rows between (the page chain has to step over them)."""
    rng = np.random.default_rng(34)
    q_lens, kv_lens = [], []
    for i, n in enumerate(BOUNDARY_LENS):
        q_lens += [n, 0]
        kv_lens += [n + (0, 5, 90, 131)[i % 4] * (ps // 16), 0]
    mp = -(-max(kv_lens) // ps)
    case = _mixed_case(rng, Hq, Hkv, D, ps, mp * len(q_lens), mp, q_lens, kv_lens)
    _assert_kernel_matches(case, Hkv, window=window)


def test_one_instance_serves_a_windowed_and_a_global_layer():
    """The window as an operand (a model that mixes layer kinds): the same
    compiled launch answers a windowed layer and, handed GLOBAL_WINDOW, a
    global one, each as the twin with that window static."""
    rng = np.random.default_rng(35)
    Hq, Hkv, D, ps, mp = 8, 4, 64, 16, 12
    q_lens, kv_lens = [1, 100, 0, 1, 7], [90, 180, 0, 9, 60]
    case = _mixed_case(rng, Hq, Hkv, D, ps, mp * len(q_lens), mp, q_lens, kv_lens)
    launch = jax.jit(lambda w: ragged_paged_attention_tpu(*case, Hkv, interpret=True, window_dyn=w))
    for operand, static in ((24, 24), (GLOBAL_WINDOW, None)):
        ref = ragged_paged_attention_jax(*case, Hkv, window=static)
        np.testing.assert_allclose(np.asarray(launch(jnp.int32(operand))), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    assert launch._cache_size() == 1
    # and the dispatch hands the operand to the twin off the chip
    np.testing.assert_allclose(
        np.asarray(ragged_paged_attention(*case, Hkv, window_dyn=jnp.int32(24))),
        np.asarray(ragged_paged_attention_jax(*case, Hkv, window=24)), rtol=1e-6, atol=1e-6)


def test_classic_decode_kernel_handles_misaligned_folded_axis():
    """The classic decode kernel rides the same lane-padded scratch: a
    192-wide folded axis (Hkv=3 · D=64) — a documented gather-forcing
    layout before ISSUE 12 — now matches the reference in interpret
    mode."""
    rng = np.random.default_rng(5)
    B, Hq, Hkv, D, ps, P, mp = 3, 6, 3, 64, 16, 32, 8
    q = jnp.asarray(rng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    pt = jnp.asarray(rng.permutation(P)[: B * mp].reshape(B, mp).astype(np.int32))
    lengths = jnp.asarray([37, 1, 101], jnp.int32)
    ref = paged_attention_jax(q, k, v, pt, lengths, Hkv)
    out = paged_attention_tpu(q, k, v, pt, lengths, Hkv, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ISSUE 44: one-query rows to the decode kernel, several pages a visit
# ---------------------------------------------------------------------------
def _packed_case(rng, Hq, Hkv, D, ps, mp, rows, dtype=np.float32, first=lambda q_len: q_len == 1):
    """``rows`` = [(q_len, kv_len)] by slot, packed as the scheduler packs a
    mixed step: the rows ``first`` says (the one-query rows) in slot order,
    then the others."""
    q_lens = np.asarray([ql for ql, _ in rows], np.int32)
    kv_lens = np.asarray([kl for _, kl in rows], np.int32)
    q_starts, off = np.zeros(len(rows), np.int32), 0
    for r in sorted(range(len(rows)), key=lambda r: not first(q_lens[r])):
        q_starts[r], off = off, off + q_lens[r]
    P = mp * len(rows)
    q = jnp.asarray(rng.normal(size=(max(off, 1), Hq, D)).astype(dtype))
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(dtype))
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(dtype))
    pt = jnp.asarray(rng.permutation(P).reshape(len(rows), mp).astype(np.int32))
    return q, k, v, pt, jnp.asarray(q_starts), jnp.asarray(q_lens), jnp.asarray(kv_lens)


def _windowed(window, how):
    """(the kernel's window arguments, the twin's window)."""
    if how == "operand":
        return {"window_dyn": jnp.int32(window or GLOBAL_WINDOW)}, window
    return {"window": window}, window


ROW_VISIT = 3  # the pages a visit the one-query rows' kernel is told below


@pytest.mark.parametrize("how", ["static", "operand"])
@pytest.mark.parametrize("window", [None, 40], ids=["global", "w40"])
@pytest.mark.parametrize("pages", [1, ROW_VISIT - 1, ROW_VISIT, ROW_VISIT + 1, 3 * ROW_VISIT + 2])
def test_one_query_rows_of_every_length_beside_a_chunk(pages, window, how):
    """One-query rows of ``pages`` pages (one ending mid-page, one ON the
    page boundary), a row of one token in all and an inactive row between
    them, beside a chunk: the decode kernel walks them ROW_VISIT pages a
    visit and each lands on its own packed position."""
    rng = np.random.default_rng(44)
    Hq, Hkv, D, ps, mp = 8, 4, 64, 16, 12
    rows = [(1, pages * ps - 5), (37, 150), (0, 0), (1, pages * ps), (1, 1), (0, 0), (1, 70)]
    case = _packed_case(rng, Hq, Hkv, D, ps, mp, rows)
    kw, twin_window = _windowed(window, how)
    ref = ragged_paged_attention_jax(*case, Hkv, window=twin_window)
    out = ragged_paged_attention_tpu(*case, Hkv, interpret=True, row_pages_per_visit=ROW_VISIT, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,first", [
    # The chunk's last token is a row of one query packed behind the decode
    # rows: inside the prefix, so the decode kernel's.
    pytest.param([(1, 90), (1, 33), (1, 161)], lambda q: True, id="tail_inside_the_prefix"),
    # Behind a chunk of 40: past the first R packed positions, so the ragged
    # kernel's, a wide tile of which one query is live.
    pytest.param([(1, 90), (40, 75), (1, 33), (1, 161)], lambda q: False, id="tail_behind_a_chunk"),
    # Packed in slot order, nothing first: one-query rows on both sides of the prefix's end.
    pytest.param([(1, 90), (1, 20), (30, 64), (1, 33), (1, 161), (0, 0)], lambda q: False, id="slot_order"),
])
def test_a_chunks_tail_of_one_query_lands_on_its_own_packed_position(rows, first):
    rng = np.random.default_rng(45)
    Hq, Hkv, D, ps, mp = 8, 4, 64, 16, 12
    case = _packed_case(rng, Hq, Hkv, D, ps, mp, rows, first=first)
    ref = ragged_paged_attention_jax(*case, Hkv)
    out = ragged_paged_attention_tpu(*case, Hkv, interpret=True, row_pages_per_visit=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert np.all(np.abs(np.asarray(out)[:sum(ql for ql, _ in rows)]).max(axis=(1, 2)) > 0)


# (rows, window) with page 16 and tiles of 128 queries: a 140-query chunk at
# 500 is two tiles, the first of which starts at position 360.
WINDOW_EDGES = {
    # 360 + 1 - 150 = 211: the near edge 3 tokens into page 13
    "edge_mid_page": ([(1, 300), (140, 500), (1, 77)], 150),
    # 360 + 1 - 153 = 208 = 13 x 16: the first visible key opens page 13
    "edge_on_a_page_boundary": ([(1, 300), (140, 500), (1, 77)], 153),
    # shorter than the chunk: a tile's last queries cannot see its first keys
    "edge_inside_the_chunk": ([(1, 300), (140, 500), (0, 0), (1, 77)], 24),
    # rows of 3 at 45 and 5 at 37 under 6: diagonal and window edge on ONE page
    "diagonal_page_is_the_edge_page": ([(3, 45), (1, 300), (5, 37)], 6),
}


@pytest.mark.parametrize("how", ["static", "operand"])
@pytest.mark.parametrize("visit", [1, 2, 3])
@pytest.mark.parametrize("case", list(WINDOW_EDGES))
def test_a_wide_tiles_visits_mask_the_diagonal_and_the_windows_edge_alone(case, visit, how):
    """The wide tile walked ``visit`` pages a visit: interior visits carry no
    mask, the ones that hold the tile's diagonal or its window's near edge
    do, wherever the edge falls; poisoned pages before a row's window cannot
    move its output."""
    rng = np.random.default_rng(46)
    Hq, Hkv, D, ps, mp = 8, 4, 64, 16, 32
    rows, window = WINDOW_EDGES[case]
    args = _packed_case(rng, Hq, Hkv, D, ps, mp, rows)
    kw, _ = _windowed(window, how)
    ref = ragged_paged_attention_jax(*args, Hkv, window=window)
    out = ragged_paged_attention_tpu(*args, Hkv, interpret=True, pages_per_visit=visit, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    # and the host's count of it: some visit unmasked where the walk is long, masked <= walked
    plan = {**PLAN, "wide_pages_per_visit": visit}
    wide = [(ql, kl) for ql, kl in rows if ql > 1]
    walked, masked = ragged_walk_pages(wide, [(window, 1)], plan, ps)
    assert 0 < masked <= walked
    assert (masked < walked) == (case in ("edge_mid_page", "edge_on_a_page_boundary")), (walked, masked)


@pytest.mark.parametrize("Hq,Hkv,D,ps,mp,rows,window", [
    # Phi-4-mini-flash's padded-query layout: one-query rows past the 512
    # window beside a short prompt, the window an operand.
    pytest.param(40, 10, 128, 128, 12, [(1, 700), (1, 1500), (190, 190), (1, 513)], 512, id="phi4_w512"),
    # The same rows in a full layer of that model: GLOBAL_WINDOW.
    pytest.param(40, 10, 128, 128, 12, [(1, 700), (1, 1500), (190, 190), (1, 513)], None, id="phi4_global"),
    # LFM2's heads of 64 over 8 KV heads.
    pytest.param(32, 8, 64, 128, 8, [(1, 300), (1, 1000), (0, 0), (150, 600), (1, 1)], None, id="heads_of_64"),
])
def test_one_query_rows_and_a_chunk_at_the_hybrids_layout_and_heads_of_64(Hq, Hkv, D, ps, mp, rows, window):
    rng = np.random.default_rng(47)
    args = _packed_case(rng, Hq, Hkv, D, ps, mp, rows)
    kw, _ = _windowed(window, "operand")
    ref = ragged_paged_attention_jax(*args, Hkv, window=window)
    out = ragged_paged_attention_tpu(*args, Hkv, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def _visits_by_hand(q_len, kv_len, window, W, N, ps):
    """(pages walked, pages in a visit that holds a pair the twin masks) of one
    row in one layer: every (query, key) pair of every visit looked at."""
    walked = masked = 0
    for start in range(kv_len - q_len, kv_len, W):
        qpos = np.arange(start, min(start + W, kv_len))[:, None]
        last = -(-(qpos[-1, 0] + 1) // ps)
        first = max(start + 1 - window, 0) // ps if window else 0
        for p0 in range(first // N * N, last, N):  # visits begin at multiples of N
            keys = np.arange(p0 * ps, (p0 + N) * ps)[None, :]  # the buffer whole: what it holds beside the walk is masked
            seen = (keys <= qpos) & ((keys > qpos - window) if window else True)
            pages = min(p0 + N, last) - max(p0, first)
            walked += pages
            masked += pages * (not seen.all())
    return walked, masked


@pytest.mark.parametrize("window", [None, 24, 100, 512])
@pytest.mark.parametrize("visit", [1, 2, 4])
def test_the_hosts_page_count_is_the_kernels_branch(visit, window):
    """``ragged_walk_pages`` (the timeline's attn_pages_walked / _masked) says a
    visit is masked exactly where it holds a pair the twin masks."""
    rng = np.random.default_rng(48)
    plan = {**PLAN, "wide_tile": 32, "wide_pages_per_visit": visit}
    rows = [(int(q), int(q + c)) for q, c in zip(rng.integers(2, 150, 12), rng.integers(0, 700, 12))]
    by_hand = np.sum([_visits_by_hand(ql, kl, window, 32, visit, 16) for ql, kl in rows], axis=0)
    assert ragged_walk_pages(rows, [(window, 1)], plan, 16) == tuple(by_hand)
    # layers of each kind add up
    both = ragged_walk_pages(rows, [(window, 3), (None, 2)], plan, 16)
    alone = ragged_walk_pages(rows, [(None, 1)], plan, 16)
    assert both == tuple(3 * a + 2 * b for a, b in zip(by_hand, alone))
    # with the lever off every page is masked
    assert len(set(ragged_walk_pages(rows, [(window, 1)], {**plan, "unmasked_visits": False}, 16))) == 1


@pytest.mark.parametrize("module,preset,kinds", [
    ("llama", "test-tiny", [(None, 2)]),                  # no window: every layer walks whole
    ("llama", "mistral-7b", [(4096, 32)]),                # one window for all
    ("mixtral", "trinity-test-tiny", [(16, 6), (None, 2)]),   # three windowed, one global, twice over
    ("mixtral", "lfm2-test-tiny", [(None, 2)]),           # its two attention layers of eight
    ("phi4flash", "phi4flash-test-tiny", [(16, 2), (None, 1)]),  # the self-decoder's; cross layers read one row each
])
def test_the_engine_counts_a_steps_pages_over_each_kind_of_attention_layer(module, preset, kinds):
    """``Engine.attn_pages`` (the timeline's attn_pages_walked / _masked) hands
    ``ragged_walk_pages`` the model's attention layers by window."""
    import importlib
    import types

    from inference_gateway_tpu.serving.engine import Engine, MixedRow, attention_layer_windows

    cfg = importlib.import_module(f"inference_gateway_tpu.models.{module}").PRESETS[preset]
    assert attention_layer_windows(cfg) == kinds
    plan = {**PLAN, "wide_tile": 32, "wide_pages_per_visit": 2}
    engine = types.SimpleNamespace(model_cfg=cfg, ragged_attention_plan=plan,
                                   config=types.SimpleNamespace(page_size=16))
    rows = [MixedRow(slot=3, token_ids=list(range(70)), start=130, kind="prefill"),
            MixedRow(slot=5, token_ids=list(range(9)), start=0, kind="prefill")]
    assert Engine.attn_pages(engine, rows) == ragged_walk_pages([(70, 200), (9, 9)], kinds, plan, 16)


@pytest.mark.parametrize("window", [None, 40, 100], ids=["global", "w40", "w100"])
@pytest.mark.parametrize("visit", [1, 2, 4])
def test_a_querys_output_does_not_depend_on_how_its_prompt_was_chunked(visit, window):
    """Bit for bit: the same queries as the tail of a 70-token chunk that
    begins mid-page (a prompt computed whole, in chunks of the step's budget)
    and as a 25-token chunk that begins on a page (the same prompt adopted
    from the prefix cache but for its last pages). Visits begin at multiples
    of the pages a visit, whatever tile walks them, so the sums run in the
    same order; the benchmark's identical probes rest on it."""
    rng = np.random.default_rng(49)
    Hq, Hkv, D, ps, mp, T = 8, 4, 64, 16, 16, 80
    kv_len = 217  # the adopted prefix ends at 192 = 12 pages; the whole chunk began at 147
    k = jnp.asarray(rng.normal(size=(2 * mp, ps, Hkv * D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2 * mp, ps, Hkv * D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(2 * mp).reshape(2, mp).astype(np.int32))
    queries = jnp.asarray(rng.normal(size=(70, Hq, D)), jnp.bfloat16)
    kw, _ = _windowed(window, "operand")

    def tail_of(q_len):
        q = jnp.zeros((T, Hq, D), jnp.bfloat16).at[:q_len].set(queries[70 - q_len:])
        row = lambda x: jnp.asarray([0, x], jnp.int32)
        out = ragged_paged_attention_tpu(q, k, v, pt, row(0), row(q_len), row(kv_len), Hkv, interpret=True,
                                         pages_per_visit=visit, **kw)
        return np.asarray(out[q_len - 25:q_len].astype(jnp.float32))

    np.testing.assert_array_equal(tail_of(70), tail_of(25))
