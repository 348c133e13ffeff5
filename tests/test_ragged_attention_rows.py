"""Ragged paged attention, the rows of ONE query and a wide tile's visits
(ISSUE 44; the kernel against its pure-JAX twin is ``tests/test_ragged_attention.py``,
of which this file was the second half until PR 58).

ISSUE 44: the launch hands the one-query rows of the packed prefix to the
decode kernel (several pages a visit) and joins the two outputs; a wide tile's
visit is several pages under one flash update, masked only on the tile's
diagonal and at its window's near edge. The cases below walk one-query rows
of every length against the pages a visit, put a chunk's tail of one query
inside and outside the prefix, and lay a window's near edge mid-page, on a
page boundary, inside the chunk and on the diagonal page, at 1, 2 and 3 pages
a visit, the window static and an operand.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.ops.paged_attention import (
    GLOBAL_WINDOW,
    ragged_paged_attention_jax,
    ragged_paged_attention_tpu,
    ragged_tile_plan,
    ragged_walk_pages,
)

# The tiling every case below is laid out against (``tests/test_ragged_attention.py``'s).
PLAN = ragged_tile_plan(8, 2, 64, 16, 10)


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

    from inference_gateway_tpu.models.family import family_of
    from inference_gateway_tpu.serving.engine import Engine, MixedRow

    cfg = importlib.import_module(f"inference_gateway_tpu.models.{module}").PRESETS[preset]
    assert list(family_of(cfg).attention_layers) == kinds
    plan = {**PLAN, "wide_tile": 32, "wide_pages_per_visit": 2}
    engine = types.SimpleNamespace(family=family_of(cfg), ragged_attention_plan=plan,
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
