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

The one-query rows' path and a wide tile's visits (ISSUE 44) are held by
``tests/test_ragged_attention_rows.py`` (split off by kernel in PR 58: under
``--dist loadfile`` this file alone was 936 s of a 1,301 s run).
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
