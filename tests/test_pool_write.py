"""A step's rows into the flat pools as copies of their runs (ISSUE 62):
``ops/pool_write.py``'s kernel, interpreted, against the scatter it stands in
for, bit for bit; the dispatch's verdicts; and a mixed step and a decode chunk
of the tiny preset that leave the same pools whichever path the dispatch takes.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from inference_gateway_tpu.models import llama
from inference_gateway_tpu.ops import pool_write
from inference_gateway_tpu.ops.pool_write import granule, pool_write_dispatch, pool_write_plan, write_rows, write_rows_tpu
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow

PAGE = 128
N = 8 * PAGE   # a pool of eight pages


def _pages(order, start, rows):
    """``rows`` indices from place ``start`` of the first page of ``order`` on."""
    return np.concatenate([p * PAGE + np.arange(PAGE) for p in order])[start:start + rows]


# name: (indices, lanes of a row, dtype)
CASES = {
    "a_512_row_chunk_from_the_middle_of_a_page_over_four_pages": (_pages([5, 2, 7, 0, 3], 77, 512), 128, jnp.bfloat16),
    "two_prompts_chunks_and_decode_rows_in_one_step": (
        np.r_[[3 * PAGE + 9, 6 * PAGE + 127, 1 * PAGE], _pages([4, 0], 100, 90), _pages([7], 3, 41)], 128, jnp.bfloat16),
    "single_rows_only": (np.array([5 * PAGE + 3, 17, 2 * PAGE + 64, 7 * PAGE + 127, 3 * PAGE]), 128, jnp.bfloat16),
    "every_row_dropped": (np.full(24, N), 128, jnp.bfloat16),
    "a_dropped_tail": (np.r_[_pages([6], 20, 50), np.full(14, N)], 128, jnp.bfloat16),
    "dropped_rows_inside_a_run": (np.r_[200 + np.arange(6), [N, N + 7], 208 + np.arange(9)], 128, jnp.bfloat16),
    "neighbours_that_are_a_permutation_of_a_contiguous_range": (
        np.r_[[3, 2, 1, 0, 19, 18, 17, 16], 40 + np.random.default_rng(3).permutation(24)], 128, jnp.bfloat16),
    "two_runs_that_take_turns_in_two_tiles": (np.array([5, 21, 6, 22, 7, 23, 8, 24]), 128, jnp.bfloat16),
    "an_index_in_the_last_row_and_one_just_past_it": (np.array([N - 2, N - 1, N, N + 1, 0]), 128, jnp.bfloat16),
    "a_negative_index_as_at_reads_it": (np.array([-1, 4, -N + 9]), 128, jnp.bfloat16),
    "rows_that_are_no_multiple_of_the_granule": (np.r_[[700], _pages([1, 4], 120, 36)], 128, jnp.bfloat16),
    "rows_of_512_lanes": (np.r_[[900, 33], _pages([2, 6], 101, 60)], 512, jnp.bfloat16),
    "rows_of_1024_lanes": (np.r_[[900, 33], _pages([2, 6], 101, 60)], 1024, jnp.bfloat16),
    "rows_of_4096_lanes": (np.r_[[900], _pages([2, 6], 123, 19)], 4096, jnp.bfloat16),
    "a_float32_pool": (np.r_[[900, 33], _pages([2, 6], 101, 60), [N]], 256, jnp.float32),
}


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16 if a.dtype == jnp.bfloat16 else jnp.uint32))


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_leaves_the_pools_the_scatter_leaves_bit_for_bit(case):
    idx, width, dtype = CASES[case]
    rng = np.random.default_rng(len(case))
    draw = lambda rows: jnp.asarray(rng.standard_normal((rows, width)), dtype)
    pools, rows, idx = (draw(N), draw(N)), (draw(len(idx)), draw(len(idx))), jnp.asarray(idx, jnp.int32)
    want = [p.at[idx].set(r, mode="drop") for p, r in zip(pools, rows)]
    got = write_rows_tpu(pools, rows, idx, interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def test_a_skip_layer_writes_nothing_through_the_kernel(monkeypatch):
    """``_write_kv`` under ``skip`` (a state layer of a model of two kinds) hands
    the kernel indices that are all the pool's row count: the pools come back
    as they were, and a layer that is not skipped writes what the scatter writes."""
    monkeypatch.setattr(pool_write, "pool_write_dispatch", lambda *a, **k: ("runs", "the test's"))
    rng = np.random.default_rng(0)
    flat, layers, width = 4 * 16, 3, 128
    ck, cv = (jnp.asarray(rng.standard_normal((layers * flat, width)), jnp.bfloat16) for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((1, 24, width)), jnp.bfloat16) for _ in range(2))
    write_idx = jnp.asarray(np.r_[16 + np.arange(20), [flat] * 4], jnp.int32)[None]
    qkv = (None, None, None, k, v)
    for skip in (True, False):
        gk, gv, w_idx = llama._write_kv(ck, cv, qkv, write_idx, 1, flat, layers * flat, skip=jnp.bool_(skip))
        np.testing.assert_array_equal(_bits(gk), _bits(ck.at[w_idx].set(k, mode="drop")))
        np.testing.assert_array_equal(_bits(gv), _bits(cv.at[w_idx].set(v, mode="drop")))
        assert bool(jnp.array_equal(gk, ck)) == skip


@pytest.mark.parametrize("rows,width,dtype,pool_rows,platform,sharded,path", [
    (517, 4096, jnp.bfloat16, 430080, "tpu", False, "runs"),       # EvaByte's mixed step
    (536, 1024, jnp.bfloat16, 1572864, "tpu", False, "runs"),      # Mistral's
    (576, 512, jnp.bfloat16, 1 << 20, "tpu", False, "runs"),       # LFM2's
    (64, 512, jnp.bfloat16, 1 << 20, "tpu", False, "scatter"),     # the decode programs' rows
    (5, 4096, jnp.bfloat16, 430080, "tpu", False, "scatter"),
    (536, 1024, jnp.bfloat16, 1572864, "cpu", False, "scatter"),   # off the chip
    (536, 1024, jnp.bfloat16, 1572864, "tpu", True, "scatter"),    # a sharded pool
    (536, 32, jnp.float32, 4096, "tpu", False, "scatter"),         # rows off the lanes
    (536, 1024, jnp.int8, 1572864, "tpu", False, "scatter"),       # one-byte rows
    (4096, 4096, jnp.bfloat16, 430080, "tpu", False, "scatter"),   # rows that do not stand in VMEM
])
def test_the_dispatch_is_a_function_of_the_shape_the_platform_and_the_mesh(rows, width, dtype, pool_rows, platform,
                                                                          sharded, path):
    got, reason = pool_write_dispatch(rows, width, dtype, pool_rows, platform=platform, sharded=sharded)
    assert got == path and reason
    plan = pool_write_plan({"mixed_step": rows}, width, dtype, pool_rows, platform, sharded)
    assert plan["tile_rows"] == granule(dtype) and plan["mixed_step"]["path"] == path
    assert plan["mixed_step"]["rows"] == rows and plan["mixed_step"]["row_bytes"] == width * jnp.dtype(dtype).itemsize


def test_off_the_chip_write_rows_is_the_scatter():
    pools = (jnp.zeros((64, 128), jnp.bfloat16),) * 2
    rows = (jnp.ones((2, 3, 128), jnp.bfloat16),) * 2
    idx = jnp.asarray([[1, 2, 64], [9, 64, 10]], jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda p, r, i: write_rows(p, r, i))(pools, rows, idx))
    assert "scatter" in jaxpr and "pallas_call" not in jaxpr


def _engine():
    return Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=256, dtype="float32", max_prefill_batch=2,
                               use_mesh=False, prefill_buckets=(16, 32, 64), decode_chunk=4, attention="paged",
                               page_size=16, mixed_step=True))


def _serve(engine):
    """A mixed step (two prompt chunks from the middle of a page, then a chunk beside
    two decoding rows) and a decode chunk; returns the pools."""
    rng = np.random.default_rng(5)
    toks = lambda n: [int(t) for t in rng.integers(1, 250, n)]
    step = lambda rows: engine.mixed_step_fetch(engine.mixed_step_submit(rows))
    step([MixedRow(0, toks(21), 0, "prefill"), MixedRow(1, toks(37), 0, "prefill")])
    step([MixedRow(0, toks(1), 21), MixedRow(1, toks(1), 37), MixedRow(2, toks(30), 0, "prefill")])
    engine.decode_chunk(np.array([5, 6, 7, 0], np.int32), np.array([22, 38, 30, 0], np.int32),
                        np.array([True, True, True, False]), np.zeros(4, np.float32), np.ones(4, np.float32))
    return {name: np.asarray(engine.cache[name]) for name in ("k", "v")}


def test_a_mixed_step_and_a_decode_chunk_leave_the_same_pools_whichever_path_the_dispatch_takes(monkeypatch):
    engine = _engine()
    assert engine.pool_write_plan["mixed_step"]["path"] == engine.pool_write_plan["decode_chunk"]["path"] == "scatter"
    want = _serve(engine)
    monkeypatch.setattr(pool_write, "pool_write_dispatch", lambda *a, **k: ("runs", "the test's"))
    engine = _engine()
    assert engine.pool_write_plan["mixed_step"]["path"] == "runs"   # the plan reads the verdict the trace takes
    got = _serve(engine)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name])
    assert np.any(want["k"] != 0)


def test_the_engine_counts_a_mixed_steps_copies_from_its_rows():
    engine = _engine()
    plan = engine.pool_write_plan
    assert plan["tile_rows"] == 8 and set(plan) == {"tile_rows", "decode_chunk", "mixed_step"}
    assert plan["mixed_step"]["rows"] == engine.mixed_budget and plan["decode_chunk"]["rows"] == 4
    # 21 rows from row 0: tiles [0, 8) [8, 16) [16, 21) = 3; one row: 1; 30 rows from row 5: 5..7, three whole, 32..34 = 5
    rows = [MixedRow(0, [1] * 21, 0, "prefill"), MixedRow(1, [1], 37), MixedRow(2, [1] * 30, 5, "prefill")]
    assert engine.write_copies(rows) == 3 + 1 + 5
