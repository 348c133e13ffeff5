"""The one-token state updates' kernels (ISSUE 63), both at once:
``ops/ssd.state_update_tpu`` (Mamba-2) and ``ops/kda.state_update_tpu`` (KDA)
walk the rows that advance (``ops/ssd.walk_rows``), a visit moving
``visit_heads`` heads' states of one row in and back out by DMA. Interpreted
off the TPU, against their twins (``state_update``) and the token recurrence;
``tests/test_chip_compile.py`` lowers them for the chip at the cells' shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.ops import kda, ssd

LAYERS, SLOTS = 3, 6
# which rows advance / which of them start from zero
ADVANCING = {
    "none": ([0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0]),
    "one": ([0, 0, 0, 1, 0, 0], [0, 0, 0, 0, 0, 0]),
    "all": ([1, 1, 1, 1, 1, 1], [0, 0, 1, 0, 0, 0]),
    "every_other": ([1, 0, 1, 0, 1, 0], [0, 0, 0, 0, 1, 0]),
    "first_idle": ([0, 1, 1, 1, 1, 1], [0, 1, 0, 0, 0, 1]),
    "last_idle": ([1, 1, 1, 1, 1, 0], [1, 0, 0, 0, 0, 0]),
    "fresh_rows_that_do_not_advance": ([0, 1, 0, 0, 1, 0], [1, 1, 1, 0, 0, 1]),
}


def _ssd_case(rng, Hh, P, N, slots=SLOTS):
    store = rng.normal(size=(LAYERS, slots, Hh, P, N)).astype(np.float32)
    dt = np.abs(rng.normal(size=(slots, Hh))).astype(np.float32) * 0.3
    x = rng.normal(size=(slots, Hh, P)).astype(np.float32)
    B, C = (rng.normal(size=(slots, N)).astype(np.float32) for _ in range(2))
    A, D = -np.linspace(0.5, 4, Hh).astype(np.float32), np.linspace(0.5, 2, Hh).astype(np.float32)
    return store, (dt, x, B, C, A, D)


def _kda_case(rng, Hh, K, slots=SLOTS):
    store = rng.normal(size=(LAYERS, slots, Hh, K, K)).astype(np.float32)
    unit = lambda a: (a / np.linalg.norm(a, axis=-1, keepdims=True)).astype(np.float32)
    q, k = unit(rng.normal(size=(slots, Hh, K))) * K ** -0.5, unit(rng.normal(size=(slots, Hh, K)))
    v = rng.normal(size=(slots, Hh, K)).astype(np.float32)
    g = -np.exp(rng.normal(size=(slots, Hh, K)) * 1.5 - 3.0).astype(np.float32)
    beta = (1 / (1 + np.exp(-rng.normal(size=(slots, Hh))))).astype(np.float32)
    return store, (q, k, v, g, beta)


def _one_step(which, first, operands, r):
    """Row r's next state and output by the token recurrence, from ``first``."""
    if which == "ssd":
        dt, x, B, C, A, D = operands
        y, state = ssd.recurrence(*map(jnp.asarray, (first, x[r:r + 1], dt[r:r + 1], B[r:r + 1], C[r:r + 1], A)))
        return np.asarray(y[0]) + D[:, None] * x[r], np.asarray(state)
    o, state = kda.recurrence(jnp.asarray(first), *(jnp.asarray(a[r:r + 1]) for a in operands))
    return np.asarray(o[0]), np.asarray(state)


def _held_to_twin_and_recurrence(which, store, operands, valid, fresh, ordinal=1):
    module = {"ssd": ssd, "kda": kda}[which]
    valid, fresh = np.asarray(valid, bool), np.asarray(fresh, bool)
    args = [jnp.asarray(a) for a in (*operands, valid, fresh)]
    out, new = module.update_store(jnp.asarray(store), ordinal, *args, kernel=True)
    want_out, want_new = module.update_store(jnp.asarray(store), ordinal, *args, kernel=False)
    out, new = np.asarray(out), np.asarray(new)
    # In place at the layer ordinal alone, and a slot that does not advance keeps its state to the BIT.
    for entry in range(store.shape[0]):
        if entry != ordinal:
            assert np.array_equal(new[entry], store[entry])
    assert np.array_equal(new[ordinal][~valid], store[ordinal][~valid])
    np.testing.assert_allclose(new[ordinal], np.asarray(want_new[ordinal]), atol=1e-5)
    np.testing.assert_allclose(out[valid], np.asarray(want_out)[valid], atol=5e-5)
    for r in np.flatnonzero(valid):
        first = np.zeros_like(store[ordinal, r]) if fresh[r] else store[ordinal, r]
        step_out, step_state = _one_step(which, first, operands, r)
        np.testing.assert_allclose(out[r], step_out, atol=5e-5)
        np.testing.assert_allclose(new[ordinal, r], step_state, atol=1e-5)


@pytest.mark.parametrize("pattern", list(ADVANCING))
@pytest.mark.parametrize("which", ["ssd", "kda"])
def test_the_walk_of_the_advancing_rows_is_the_twin_and_the_recurrence(monkeypatch, which, pattern):
    """Two visits a row (a visit's bytes cut to half a row's states), every way
    the advancing rows can lie among the slots: none, one, all, every other one,
    the first or the last slot idle, fresh rows among them and among the idle."""
    rng = np.random.default_rng(list(ADVANCING).index(pattern))
    if which == "ssd":
        store, operands = _ssd_case(rng, 16, 16, 128)
        monkeypatch.setattr(ssd, "VISIT_BYTES", 8 * 16 * 128 * 4)
        assert ssd.update_plan(store.shape)["visits_a_row"] == 2
    else:
        store, operands = _kda_case(rng, 4, 32)
        monkeypatch.setattr(ssd, "VISIT_BYTES", 2 * 32 * 32 * 4)
        assert kda.update_plan(store.shape)["visits_a_row"] == 2
    _held_to_twin_and_recurrence(which, store, operands, *ADVANCING[pattern])


@pytest.mark.parametrize("which,heads,rows,visit_heads", [
    ("ssd", 12, 16, 6),     # 8 heads of 16 rows fill a 128-row tile; 12 heads' widest divisor under them is 6: ONE tile of 96 rows
    ("ssd", 2, 256, 2),     # a head of 256 rows is two tiles: the decay is one scalar a tile
    ("ssd", 8, 16, 8),      # a row's states in one visit
    ("kda", 6, 32, 3),      # 4 heads a visit do not divide 6: three
    ("kda", 2, 32, 2),
], ids=["ssd_12_heads", "ssd_a_head_of_two_tiles", "ssd_one_visit", "kda_6_heads", "kda_one_visit"])
def test_the_heads_of_a_visit_follow_the_shape(monkeypatch, which, heads, rows, visit_heads):
    """A visit holds the most heads that divide the head count under its bytes:
    where the widest block does not divide, the next divisor (and, on the chip,
    the path falls back by shape where a visit is then off the 128-row tiles)."""
    rng = np.random.default_rng(heads)
    if which == "ssd":
        store, operands = _ssd_case(rng, heads, rows, 128)
        monkeypatch.setattr(ssd, "VISIT_BYTES", max(8 * 16, rows * 2) * 128 * 4)
        plan = ssd.update_plan(store.shape, "tpu")
        assert plan["path"] == ("state_update" if (visit_heads * rows) % 128 else "ssm_state_update_kernel"), plan
    else:
        store, operands = _kda_case(rng, heads, rows)
        monkeypatch.setattr(ssd, "VISIT_BYTES", 4 * rows * rows * 4)
        plan = kda.update_plan(store.shape, "tpu")
        assert plan["path"] == "state_update" and "128 x 128" in plan["reason"]     # a head's state of 32 x 32
    assert plan["visit_heads"] == visit_heads and plan["visits_a_row"] == heads // visit_heads
    _held_to_twin_and_recurrence(which, store, operands, [1, 0, 1, 1, 0, 1], [0, 0, 0, 1, 0, 0], ordinal=2)


@pytest.mark.parametrize("which,shape,heads,visit_bytes", [
    ("ssd", (9, 32, 128, 64, 128), 32, 1 << 20),     # Granite's cell
    ("ssd", (9, 64, 128, 64, 128), 32, 1 << 20),     # ROADMAP W9's 64 slots
    ("kda", (6, 32, 32, 128, 128), 16, 1 << 20),     # Kimi's cell
    ("ssd", (4, 8, 48, 64, 128), 24, 3 << 18),       # 32 heads do not divide 48: 24
    ("kda", (2, 8, 24, 128, 128), 12, 3 << 18),
], ids=["granite", "granite_64_slots", "kimi", "ssd_48_heads", "kda_24_heads"])
def test_the_plan_of_the_cells_shapes_is_a_megabyte_a_visit(which, shape, heads, visit_bytes):
    """``update_plan`` is a function of the static shape and the platform alone."""
    module, name = {"ssd": (ssd, "ssm_state_update_kernel"), "kda": (kda, "kda_state_update_kernel")}[which]
    plan = module.update_plan(shape, "tpu")
    assert (plan["path"], plan["visit_heads"], plan["visit_bytes"]) == (name, heads, visit_bytes), plan
    assert plan["visits_a_row"] == shape[2] // heads
    off = module.update_plan(shape, platform="cpu")
    assert off["path"] == "state_update" and "cpu" in off["reason"] and off["visit_heads"] == heads


@pytest.mark.parametrize("which", ["ssd", "kda"])
def test_off_the_chip_the_store_takes_the_definition_and_a_lane_count_off_128_falls_back(which):
    """``update_store(kernel=None)`` follows ``update_plan``: off the TPU the plain
    ``state_update``, and on it the same where the state is off the 128 lanes."""
    shape = (2, 4, 8, 16, 64) if which == "ssd" else (2, 4, 2, 64, 64)
    module = {"ssd": ssd, "kda": kda}[which]
    assert module.update_plan(shape, "tpu")["path"] == "state_update"
    rng = np.random.default_rng(0)
    store, operands = _ssd_case(rng, 8, 16, 64, 4) if which == "ssd" else _kda_case(rng, 2, 64, 4)
    valid = jnp.asarray([1, 0, 1, 1], bool)
    out, new = jax.jit(lambda s: module.update_store(s, 0, *map(jnp.asarray, operands), valid))(jnp.asarray(store))
    assert np.array_equal(np.asarray(new[1]), store[1]) and np.array_equal(np.asarray(new[0, 1]), store[0, 1])
    assert not np.array_equal(np.asarray(new[0, 0]), store[0, 0]) and out.shape == operands[1 if which == "ssd" else 2].shape


def test_the_advancing_rows_stand_in_slot_order_in_front():
    order, n = ssd.advancing_rows(jnp.asarray([0, 1, 0, 1, 1, 0], bool))
    assert order.tolist()[:3] == [1, 3, 4] and sorted(order.tolist()) == list(range(6)) and n.tolist() == [3]
    order, n = ssd.advancing_rows(jnp.zeros(4, bool))
    assert n.tolist() == [0] and order.dtype == jnp.int32
