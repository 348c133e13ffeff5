"""The expert kernels' visit table, row layout and the kernel itself at the
shapes the layers' own tests never reach (ISSUE 42): more than 16 rows an
expert (a visit of 64 rows in four granules, of 128 past 64 rows an expert),
128 experts, one expert that holds ten times the mean (several visits),
experts with no rows, a shard whose experts lie in the middle of the range."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from inference_gateway_tpu.ops import moe
from inference_gateway_tpu.ops.quant import QTensor, quantize_tensor


def _sizes(seed: int, experts: int, rows: int, empty: int, favourite: float) -> np.ndarray:
    """Rows each of ``experts`` experts holds, ``rows`` in all: ``empty``
    experts hold none, expert 1 ``favourite`` times the others' odds."""
    rng = np.random.default_rng(seed)
    odds = np.ones(experts)
    odds[1] = favourite
    odds[rng.permutation(np.arange(2, experts))[:empty]] = 0.0
    return np.bincount(rng.choice(experts, size=rows, p=odds / odds.sum()), minlength=experts).astype(np.int32)


VISIT_CASES = {
    # (experts, rows, experts with no rows, the favourite's odds)
    "decode_few_rows_an_expert": (64, 288, 0, 1.0),
    "decode_fewer_rows_than_experts": (128, 96, 0, 1.0),
    "mixed_even": (128, 4192, 0, 1.0),
    "mixed_one_expert_ten_times_the_mean": (128, 4352, 21, 12.0),
    "mixed_eight_experts_past_a_visit_each": (8, 1072, 1, 3.0),
    "one_expert_holds_every_row": (16, 640, 15, 1.0),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", list(VISIT_CASES))
def test_every_live_row_is_covered_by_exactly_one_visit_of_its_own_expert(case, seed):
    E, M, empty, favourite = VISIT_CASES[case]
    sizes = _sizes(seed, E, M, empty, favourite)
    plan = moe.visit_plan(M, E)
    g, c, rows, V = plan
    gid, chunk0, cnt, holds, num = (np.asarray(a) for a in moe._visits(jnp.asarray(sizes), plan))
    num = int(num[0])
    assert gid.shape == chunk0.shape == cnt.shape == (V,) and holds.shape == (c, V)
    assert num <= V and num == int(moe.visit_count(jnp.asarray(sizes), M))
    # the layout: every expert's rows from a granule boundary on, back to back
    padded = -(-sizes // g) * g
    begin = np.cumsum(padded) - padded
    assert padded.sum() <= rows
    covered = np.zeros(rows, np.int32)
    owner = np.full(rows, -1)
    for v in range(num):
        assert 1 <= cnt[v] <= c
        lo, hi = chunk0[v] * g, (chunk0[v] + cnt[v]) * g
        covered[lo:hi] += 1
        owner[lo:hi] = gid[v]
        # the row operands: the first ``cnt`` hold this visit's granules ...
        assert list(holds[:cnt[v], v]) == list(range(chunk0[v], chunk0[v] + cnt[v]))
        # ... and one the visit does not need holds what it held: no copy is made
        assert all(holds[i, v] == (holds[i, v - 1] if v else 0) for i in range(cnt[v], c))
    for e in range(E):
        live = np.arange(begin[e], begin[e] + sizes[e])
        assert (covered[live] == 1).all() and (owner[live] == e).all()
    assert covered.max(initial=0) <= 1
    # visits of one expert are consecutive, in expert order, and an expert without rows has none
    visited = gid[:num]
    assert (np.diff(visited) >= 0).all() and set(visited) == set(np.flatnonzero(sizes))
    assert all((visited == e).sum() == -(-sizes[e] // (g * c)) for e in range(E))
    # the tail repeats the last visit's blocks and moves no rows
    assert (cnt[num:] == 0).all() and (gid[num:] == (gid[num - 1] if num else gid[0])).all()
    assert (holds[:, num:] == holds[:, num - 1:num]).all() if num else True


def test_the_plan_follows_the_rows_an_expert():
    """Up to 16 rows an expert: a visit of 32 rows in granules of 16; up to 64:
    a visit of 64; beyond (Mixtral's 134): 128 in granules of 32. The layout
    pads every expert with rows at most a granule less one; the grid is the
    most visits there can be."""
    assert moe.visit_plan(288, 64)[:2] == (16, 2) and moe.visit_plan(96, 128)[:2] == (16, 2)
    assert moe.visit_plan(4352, 128)[:2] == (16, 4) and moe.visit_plan(3360, 64)[:2] == (16, 4)
    assert moe.visit_plan(1072, 8)[:2] == (32, 4)
    assert moe.visit_plan(4352, 128).rows == 4352 + 128 * 15 and moe.visit_plan(4352, 128).visits == 68 + 128
    assert moe.visit_plan(96, 128).rows == 96 * 16 and moe.visit_plan(4352, 128, 32).rows == 4352 + 32 * 15
    assert moe.visit_plan(1072, 8).rows == (1072 + 8 * 31) // 32 * 32 and moe.visit_plan(1072, 8).visits == 8 + 8


def test_the_column_block_is_sized_by_bytes():
    """Gate/up and down of the three mixtures in one block, Mixtral's 14,336
    columns still in blocks, a width that is no multiple of a lane whole."""
    assert moe._col_block(2048, 1024, 2, 1) == 1024 and moe._col_block(1024, 2048, 1, 1) == 2048
    assert moe._col_block(2560, 768, 2, 1) == 768 and moe._col_block(768, 2560, 1, 1) == 2560
    assert moe._col_block(4096, 14336, 2, 1) == 512 and moe._col_block(14336, 4096, 1, 1) == 256
    assert moe._col_block(4096, 14336, 2, 2) == 256 and moe._col_block(64, 192, 2, 4) == 192
    assert 2 * 4096 * moe._col_block(4096, 14336, 2, 1) <= moe.VISIT_BYTES


def _stacks(rng, L, E, H, I, quantized):
    ws = [jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.1) for s in ((L, E, H, I), (L, E, H, I), (L, E, I, H))]
    return [quantize_tensor(w) for w in ws] if quantized else ws


def _matrix(w, layer, e):
    if isinstance(w, QTensor):
        return np.asarray(w.q[layer, e], np.float32) * np.asarray(w.scale[layer, e])
    return np.asarray(w[layer, e])


KERNEL_CASES = {
    # (experts, this shard's, from expert, tokens, top-k, experts never chosen, the favourite's odds)
    "mixed_128_experts_one_at_ten_times_the_mean": (128, 128, 0, 288, 8, 21, 14.0),
    "mixed_a_shard_in_the_middle": (128, 32, 64, 288, 8, 21, 1.0),
    "mixed_8_experts_past_a_visit_each": (8, 8, 0, 136, 4, 2, 6.0),
    "decode_64_experts": (64, 64, 0, 48, 6, 0, 1.0),
    "decode_a_shard_in_the_middle": (64, 16, 16, 48, 6, 0, 1.0),
}


@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_interpreted_kernel_multiplies_every_held_row_by_its_own_expert(case, quantized):
    """Both kernels through the layout of ``dispatch``, against a product made
    row by row in numpy and against the twin on the same layout: every (token,
    choice) whose expert the shard holds, at its place; none of another shard's."""
    E, e_local, first, T, k, dead, favourite = KERNEL_CASES[case]
    rng = np.random.default_rng(len(case))
    L, H, I, layer = 2, 128, 256, 1
    odds = np.ones(E)
    odds[first + 1] = favourite
    odds[rng.permutation(np.setdiff1d(np.arange(E), [first + 1]))[:dead]] = 1e-9
    logits = np.log(odds)[None, :] + rng.gumbel(size=(T, E))
    expert = np.argsort(-logits, axis=1)[:, :k].reshape(-1).astype(np.int32)
    expert[-k:] = E  # the last token carries none
    x = jnp.asarray(rng.normal(size=(T, H)).astype(np.float32))
    wg, wu, wd = (jax.tree.map(lambda a: a[:, first:first + e_local], w) for w in _stacks(rng, L, E, H, I, quantized))

    lay = moe.dispatch(jnp.asarray(expert), k, E, e_local, first)
    assert lay.plan == moe.visit_plan(T * k, E, e_local)
    assert list(np.asarray(lay.all_sizes)) == list(np.bincount(expert, minlength=E + 1)[:E])
    assert list(np.asarray(lay.sizes)) == list(np.asarray(lay.all_sizes)[first:first + e_local])
    rows = x[lay.token]
    got = {}
    for kernel in (True, False):
        h = moe.grouped_matmul(rows, (wg, wu), layer, lay.sizes, lay.plan, lay.row_expert, act="relu", kernel=kernel)
        got[kernel] = np.asarray(moe.grouped_matmul(h, (wd,), layer, lay.sizes, lay.plan, lay.row_expert, kernel=kernel))
    back = np.asarray(lay.back)
    held = (expert >= first) & (expert < first + e_local)
    assert (back[~held] == lay.plan.rows).all() and len(set(back[held])) == held.sum()
    assert held.sum() == int(np.asarray(lay.sizes).sum()) and held.any()
    xs = np.asarray(x)
    for a in np.flatnonzero(held):
        e = expert[a] - first
        assert int(lay.row_expert[back[a]]) == e and int(lay.token[back[a]]) == a // k
        row = xs[a // k]
        want = (np.maximum(row @ _matrix(wg, layer, e), 0) * (row @ _matrix(wu, layer, e))) @ _matrix(wd, layer, e)
        np.testing.assert_allclose(got[True][back[a]], want, atol=3e-5, rtol=1e-4)
    np.testing.assert_allclose(got[True][back[held]], got[False][back[held]], atol=2e-5, rtol=1e-5)


def _all_experts(ws, layer=1):
    """Every expert of ``layer`` of the stacks on every token: ``moe_dense``'s ``expert_fn``."""
    from inference_gateway_tpu.ops.quant import qeinsum

    lw = [jax.tree.map(lambda a: a[layer], w) for w in ws]

    def expert_fn(inp):
        h = jax.nn.silu(qeinsum("enh,ehi->eni", inp, lw[0])) * qeinsum("enh,ehi->eni", inp, lw[1])
        return qeinsum("eni,eih->enh", h, lw[2])

    return expert_fn


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas_interpret"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize("tokens,experts,k", [(288, 128, 8), (136, 8, 4)], ids=["128_experts", "8_experts"])
def test_dropless_layer_at_a_mixed_steps_rows_an_expert_is_the_all_experts_oracle(tokens, experts, k, quantized, kernel):
    """The whole layer where a visit is 128 rows: a router that sends every
    token to expert 2 first (ten and more times the mean, several visits) and
    never to a fifth of the experts; rows without a token choose none."""
    rng = np.random.default_rng(experts)
    L, H, I = 2, 64, 128
    ws = _stacks(rng, L, experts, H, I, quantized)
    x = jnp.asarray(rng.normal(size=(tokens, H)).astype(np.float32))
    logits = rng.normal(size=(tokens, experts)).astype(np.float32)
    logits[:, 2] += 100.0
    logits[:, rng.permutation(np.arange(3, experts))[:experts // 5]] -= 100.0
    logits = jnp.asarray(logits)

    valid = jnp.arange(tokens) < tokens - 5
    want = moe.moe_dense(x, logits, k, _all_experts(ws))
    got, rows = moe.moe_dropless(x, logits, k, *ws, 1, valid=valid, kernel=kernel)
    np.testing.assert_allclose(np.asarray(got[:-5]), np.asarray(want[:-5]), atol=3e-5, rtol=1e-4)
    assert not np.asarray(got[-5:]).any()
    assert int(rows.sum()) == (tokens - 5) * k and int(rows[2]) == tokens - 5 and int((rows == 0).sum()) >= experts // 5
    assert tokens * k > 16 * experts and int(moe.visit_count(rows, tokens * k)) >= int((rows > 0).sum()) + 1


def _layer_case(seed, tokens, experts, k, quantized=False, H=64, I=128):
    """(x, router logits, stacks of two layers, the all-experts function of layer 1)."""
    rng = np.random.default_rng(seed)
    ws = _stacks(rng, 2, experts, H, I, quantized)
    x = jnp.asarray(rng.normal(size=(tokens, H)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(tokens, experts)).astype(np.float32))
    return x, logits, ws, _all_experts(ws)


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas_interpret"])
@pytest.mark.parametrize("tokens", [6, 48, 136])
@pytest.mark.parametrize("k", [3, 4, 6, 8, 10])
def test_the_combine_sums_every_choice_at_any_top_k(k, tokens, kernel):
    """ISSUE 51: the combine gathers choice-major, so k lies on no tiled axis:
    the layer is the all-experts oracle at top-3 to top-10, at a decode step's
    rows and past a mixed step's 128, with rows that carry no token among the
    others (every third) and at the end."""
    x, logits, ws, expert_fn = _layer_case(100 * k + tokens, tokens, 16, k)
    valid = (jnp.arange(tokens) % 3 != 1) & (jnp.arange(tokens) < tokens - 2)
    want = moe.moe_dense(x, logits, k, expert_fn)
    got, rows = moe.moe_dropless(x, logits, k, *ws, 1, valid=valid, kernel=kernel)
    live = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=3e-5, rtol=1e-4)
    assert not np.asarray(got)[~live].any() and int(rows.sum()) == int(live.sum()) * k


@pytest.mark.parametrize("k", [4, 8, 10])
def test_a_tokens_sum_is_the_same_to_the_bit_wherever_it_lies_and_whatever_else_the_step_holds(k):
    """The benchmark sends each probe twice and the second, which adopts the
    first's pages, lies elsewhere in a step that holds other rows: the sum over
    a token's choices is an elementwise chain in the choices' order, so the
    same rows under the same weights give the same bits at another packed
    position, in a shorter step, and with half of the other tokens dropped. A
    clamped row of no choice may hold anything (here NaN): it is selected away."""
    N, H, rows = 48, 128, 640
    rng = np.random.default_rng(k)
    y = jnp.asarray(rng.normal(size=(rows, H)).astype(np.float32)).astype(jnp.bfloat16).at[rows - 1].set(jnp.nan)
    back = rng.permutation(rows - 1)[:N * k].astype(np.int32).reshape(N, k)
    weights = jax.nn.softmax(jnp.asarray(rng.normal(size=(N, k)).astype(np.float32)), axis=-1)
    base = np.asarray(moe.combine(y, jnp.asarray(back.reshape(-1)), weights))
    assert base.dtype == np.float32 and np.isfinite(base).all()

    def bits(a):
        return np.ascontiguousarray(a).view(np.uint32)

    perm = rng.permutation(N)
    moved = np.asarray(moe.combine(y, jnp.asarray(back[perm].reshape(-1)), weights[perm]))
    assert (bits(moved) == bits(base[perm])).all()
    short = np.asarray(moe.combine(y, jnp.asarray(back[5:18].reshape(-1)), weights[5:18]))
    assert (bits(short) == bits(base[5:18])).all()
    kept = np.arange(N) % 2 == 0
    halved = np.where(kept[:, None], back, rows)  # ``rows``: this shard holds no row of the pair
    half = np.asarray(moe.combine(y, jnp.asarray(halved.reshape(-1)), weights))
    assert (bits(half[kept]) == bits(base[kept])).all() and not half[~kept].any()
    # the same through the whole layer: the kernels multiply a row by itself alone
    x, logits, ws, _ = _layer_case(k, N, 16, k)
    whole, _ = moe.moe_dropless(x, logits, k, *ws, 1, kernel=True)
    again, _ = moe.moe_dropless(x[perm], logits[perm], k, *ws, 1, valid=jnp.asarray(kept), kernel=True)
    assert (bits(np.asarray(again)[kept]) == bits(np.asarray(whole)[perm][kept])).all()


@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
def test_the_combine_on_an_ep_by_tp_mesh_is_the_all_experts_oracle(quantized):
    """ep = 2 x tp = 2: each shard combines the rows of its own experts (every
    other pair selected away) from its float32 partial sums of the down
    matrix's rows, and one psum finishes the layer: top-6 of 8 experts, 48
    tokens, three of them carrying none."""
    from jax.sharding import NamedSharding

    from inference_gateway_tpu.parallel.mesh import create_moe_mesh

    tokens, experts, k = 48, 8, 6
    x, logits, ws, expert_fn = _layer_case(51, tokens, experts, k, quantized)
    mesh = create_moe_mesh(dp=1, sp=1, ep=2, tp=2)
    specs = (moe._stack_spec(ws[0], "ep", None, "tp"), moe._stack_spec(ws[1], "ep", None, "tp"),
             moe._stack_spec(ws[2], "ep", "tp", None))
    sharded = [jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), w, spec)
               for w, spec in zip(ws, specs)]
    valid = jnp.arange(tokens) < tokens - 3
    want = moe.moe_dense(x, logits, k, expert_fn)
    got, rows = jax.jit(lambda x_, l_, *w_: moe.moe_dropless(x_, l_, k, *w_, 1, valid=valid, mesh=mesh))(
        x, logits, *sharded)
    np.testing.assert_allclose(np.asarray(got[:-3]), np.asarray(want[:-3]), atol=3e-5, rtol=1e-4)
    assert not np.asarray(got[-3:]).any() and int(rows.sum()) == (tokens - 3) * k
