"""``ops/ssm.conv_ragged`` alone (ISSUE 53): the packed convolution of a state
layer against a per-token NumPy convolution, over the three uses the models
make of it and the layouts a mixed step packs; and the invariant its two
expressions (body rows by static shifts, a segment's first K - 1 rows from the
slot's tail) have to keep: a token's float32 depends on its K inputs alone, so
a sequence run whole and the same sequence cut anywhere, at another packed
offset, with the tails carried, agree TO THE BIT.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from inference_gateway_tpu.ops import ssm

C = 64
# the three uses: Granite's and Phi-4-flash's (K 4, bias, SiLU), the same without
# the activation, LFM2's short convolution (K 3, no bias, no activation)
USES = {"k4_bias_silu": (4, True, jax.nn.silu), "k4_bias": (4, True, None), "k3_plain": (3, False, None)}


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _layout(name: str, K: int):
    """(T, q_lens, the slots in packed order, fresh). Rows of T past the live tokens are dead."""
    rng = np.random.default_rng(len(name))
    if name == "chunk_beside_one_token_rows":      # a long-context mixed step: 512 + 31 of 544
        lens = np.ones(32, np.int64)
        lens[5] = 512
        return 544, lens, np.arange(32), rng.random(32) < 0.1
    if name == "segments_shorter_than_the_tail":   # every length from 1 to K - 1, and K, K + 1
        lens = np.array([1, 2, K - 1, K, K + 1, 1, 2, 1])
        return 24, lens, np.arange(8), np.zeros(8, bool)
    if name == "empty_slots":
        lens = np.array([0, 7, 0, 0, 1, 12, 0, 3])
        return 24, lens, np.arange(8), rng.random(8) < 0.5
    if name == "fresh_and_resumed":
        lens = np.array([9, 1, 1, 2, 6, 1, 3, 1])
        return 24, lens, np.arange(8), np.array([1, 0, 1, 0, 0, 1, 1, 0], bool)
    if name == "slots_in_shuffled_order":
        lens = np.array([4, 1, 10, 2, 1, 0, 5, 1])
        return 24, lens, rng.permutation(8), rng.random(8) < 0.3
    assert name == "dead_rows_behind_the_live_ones"
    lens = np.array([3, 1, 0, 5, 1, 1, 0, 2])
    return 40, lens, rng.permutation(8), rng.random(8) < 0.3


LAYOUTS = ["chunk_beside_one_token_rows", "segments_shorter_than_the_tail", "empty_slots", "fresh_and_resumed",
           "slots_in_shuffled_order", "dead_rows_behind_the_live_ones"]


def _starts(lens, order):
    starts, at = np.zeros(len(lens), np.int64), 0
    for s in order:
        starts[s], at = at, at + lens[s]
    return starts


def _draw(rng, K, bias, S, T):
    u = np.array(jnp.asarray(rng.normal(size=(T, C)), jnp.bfloat16).astype(jnp.float32))   # bfloat16 values
    tails = rng.normal(size=(S, K - 1, C)).astype(np.float32)
    w = rng.normal(size=(K, C)).astype(np.float32)
    b = rng.normal(size=(C,)).astype(np.float32) if bias else None
    return u, tails, w, b


def _run(u, tails, w, b, starts, lens, fresh, act):
    T = u.shape[0]

    @jax.jit
    def step(u, tails, w, b, starts, lens, fresh):
        seg = ssm.token_segments(starts, lens, T)
        return ssm.conv_ragged(u, tails, w, b, seg, fresh, starts, lens, act=act)

    c, new = step(jnp.asarray(u, jnp.bfloat16), jnp.asarray(tails), jnp.asarray(w), None if b is None else jnp.asarray(b),
                  jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32), jnp.asarray(fresh))
    return np.asarray(c), np.asarray(new)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("use", list(USES))
def test_conv_ragged_is_the_per_token_convolution(use, layout):
    """Every live token against ``act(b + sum_j w[j] * x[t - K + 1 + j])`` over
    [the slot's tail, zero where fresh ; the segment], token by token in float64;
    the new tails the last K - 1 of that sequence, an empty slot's kept; dead
    rows hold NaN on the way in and reach no live token."""
    K, bias, act = USES[use]
    T, lens, order, fresh = _layout(layout, K)
    S = len(lens)
    starts = _starts(lens, order)
    live = int(lens.sum())
    u, tails, w, b = _draw(np.random.default_rng(7 * len(layout) + K), K, bias, S, T)
    u[live:] = np.nan
    c, new = _run(u, tails, w, b, starts, lens, fresh, act)
    assert c.dtype == np.float32 and c.shape == (T, C) and new.shape == tails.shape
    for s in range(S):
        seq = np.concatenate([np.where(fresh[s], 0.0, tails[s]), u[starts[s]:starts[s] + lens[s]]]).astype(np.float64)
        for i in range(lens[s]):
            acc = (b if bias else 0.0) + sum(w[j].astype(np.float64) * seq[i + j] for j in range(K))
            want = _silu(acc) if act is not None else acc
            np.testing.assert_allclose(c[starts[s] + i], want, rtol=2e-5, atol=2e-5, err_msg=f"slot {s} token {i}")
        np.testing.assert_array_equal(new[s], seq[-(K - 1):].astype(np.float32) if lens[s] else tails[s])
    assert np.isfinite(c[:live]).all()


def _bits(a):
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("cut", [1, 2, 5, 133, 134])
@pytest.mark.parametrize("use", list(USES))
def test_a_tokens_bits_do_not_depend_on_where_its_chunk_begins(use, cut):
    """One sequence of 140 tokens run whole (from packed row 0, fresh), and cut
    at ``cut``: its front behind another slot's tokens in one step, its back in a
    second step at yet another packed offset, resumed from the tails the first
    left. Results and final tails equal to the bit: a head row (its inputs
    through the slot's tail) and a body row (through its packed neighbours) that
    see the same K inputs give the same float32."""
    K, bias, act = USES[use]
    L, S, T = 140, 4, 160
    rng = np.random.default_rng(53 + K + cut)
    seq, tails, w, b = _draw(rng, K, bias, S, L)
    filler = _draw(rng, K, bias, S, T)[0]

    def step(slot_of_seq, piece, at, tails, fresh_seq):
        """``piece`` of the sequence at packed row ``at`` for ``slot_of_seq``, another slot's tokens in front."""
        u = filler.copy()
        u[at:at + len(piece)] = piece
        lens, starts, fresh = np.zeros(S, np.int64), np.zeros(S, np.int64), np.zeros(S, bool)
        other = (slot_of_seq + 1) % S
        lens[other], starts[other] = at, 0
        lens[slot_of_seq], starts[slot_of_seq], fresh[slot_of_seq] = len(piece), at, fresh_seq
        c, new = _run(u, tails, w, b, starts, lens, fresh, act)
        return c[at:at + len(piece)], new

    whole, whole_tails = step(2, seq, 0, tails, True)
    front, carried = step(2, seq[:cut], 7, tails, True)
    back, cut_tails = step(2, seq[cut:], 3, carried, False)
    np.testing.assert_array_equal(_bits(np.concatenate([front, back])), _bits(whole))
    np.testing.assert_array_equal(_bits(cut_tails[2]), _bits(whole_tails[2]))
