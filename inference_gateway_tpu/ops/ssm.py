"""Selective state-space operations (Mamba, arXiv:2312.00752) for serving: a
depthwise causal convolution and a selective scan, each in two forms.

- **decode**: every row is one slot advancing one token; the whole state
  store is read and written once, batched over the slots.
- **ragged**: the packed tokens of a mixed step (serving/engine.py). Each
  segment, a prompt chunk or a decode row of one token, starts from its
  slot's state and leaves its last state there; a segment that starts at
  position 0 starts from zero whatever the slot held, so a slot that is
  admitted again, or resumed after a preemption by recompute, needs no
  reset from the host.

The state is float32 whatever the activations are: ``S`` (slots, Di, N) and
the convolution's tail, the last K - 1 inputs of a slot (slots, K - 1, Di).
The recurrence multiplies the state by exp(Delta * A) at every token for
thousands of tokens; in bfloat16 the product drifts by a rounding a step
(tests/test_phi4flash.py holds the float32 state to the reference and shows
the bfloat16 one failing).

    c_t = silu(b + sum_j w[j] * u_{t-K+1+j})
    S_t = exp(Delta_t A) * S_{t-1} + (Delta_t c_t) B_t^T ;  y_t = S_t C_t + D * c_t

The convolution alone is also LFM2's token mixer (models/mixtral.py, ISSUE
43: K = 3, no bias, no activation), so both of its forms take the bias
(``b`` None: none) and the activation (``act`` None: linear).

The ragged convolution reads what a slot remembers only where a segment
begins (ISSUE 53). A token with ``lag`` neighbours of its own segment in front
of it takes its input ``lag`` back from the packed array itself, at a static
shift; only a segment's first K - 1 tokens, at most slots x (K - 1) rows a
step, reach into the slot's tail, and they are computed apart, from
[tail ; the segment's first K - 1 inputs], and written over the shifted form's
rows. Two expressions therefore give one token's value, depending on where its
chunk begins, and THE INVARIANT is that they agree to the bit: a token's
float32 is a function of its K inputs alone, not of its packed position nor of
where the scheduler cut the prompt (a sequence run whole and run in chunks
leaves the same state; tests/test_ssm_conv_ragged.py). What keeps it: one
order of sums (``_taps``: the lead term, then lag 0, 1, 2, .., one addition
each) in both expressions, every addition with exactly ONE product beside it,
so that a compiler which contracts multiply-adds has one way to do it (XLA's
CPU backend does contract; of ``w0 * u0 + w1 * u1`` it may keep either
product rounded, and two fusions may choose differently: with no bias the
sum starts from a zero the compiler cannot fold).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _activate(acc, b, act):
    """``act(b + acc)``; b None: no bias, act None: no activation."""
    acc = acc if b is None else b + acc
    return acc if act is None else act(acc)


def conv_decode(u, tails, w, b, valid, act=jax.nn.silu):
    """One token a slot. u (S, Di); tails (S, K-1, Di) float32, oldest first;
    w (K, Di); b (Di,) or None; valid (S,) rows that advance. Returns c (S, Di)
    float32 and the new tails."""
    with jax.named_scope("ssm.conv"):
        u = u.astype(jnp.float32)
        window = jnp.concatenate([tails, u[:, None, :]], axis=1)           # (S, K, Di)
        c = _activate(jnp.sum(window * w[None], axis=1), b, act)
        return c, jnp.where(valid[:, None, None], window[:, 1:], tails)


def scan_decode(states, delta, c, B, C, A, D, valid):
    """One token a slot. states (S, Di, N) float32; delta, c (S, Di); B, C
    (S, N); A (Di, N) negative; D (Di,). Returns y (S, Di) and the new
    states; rows not ``valid`` keep theirs."""
    with jax.named_scope("ssm.scan"):
        new = jnp.exp(delta[:, :, None] * A[None]) * states + (delta * c)[:, :, None] * B[:, None, :]
        y = jnp.einsum("sdn,sn->sd", new, C) + D * c
        return y, jnp.where(valid[:, None, None], new, states)


def token_segments(q_starts, q_lens, T: int):
    """Which slot each of T packed tokens belongs to, and its index inside
    its segment. Rows are packed back to back in any order of slots; a
    token past the last row belongs to none (slot = number of slots, which
    a scatter drops). Returns (slot (T,), local (T,), live (T,))."""
    t = jnp.arange(T)[:, None]
    member = (t >= q_starts[None, :]) & (t < (q_starts + q_lens)[None, :])  # (T, S)
    live = jnp.any(member, axis=1)
    slot = jnp.where(live, jnp.argmax(member, axis=1), q_starts.shape[0])
    local = jnp.arange(T) - q_starts[jnp.minimum(slot, q_starts.shape[0] - 1)]
    return slot.astype(jnp.int32), local.astype(jnp.int32), live


def _taps(tap, w, lead, act):
    """``act(lead + w[K-1] * tap(0) + w[K-2] * tap(1) + ...)``, summed left to
    right, one addition a lag, each with one product beside it: THE order of
    the ragged convolution's sums (``tap(lag)``: the input ``lag`` tokens back)."""
    K = w.shape[0]
    acc = lead
    for lag in range(K):
        acc = acc + w[K - 1 - lag] * tap(lag)
    return acc if act is None else act(acc)


def conv_ragged(u, tails, w, b, seg, fresh, q_starts, q_lens, act=jax.nn.silu):
    """The convolution over packed tokens. u (T, Di); tails (S, K-1, Di);
    ``seg`` = token_segments(...) (the callers' contract: the segments are read
    from ``q_starts`` / ``q_lens`` here); fresh (S,) slots whose segment starts at
    position 0 (their tail counts as zero); b (Di,) or None. Returns c (T, Di)
    float32 and the new tails: the last K - 1 inputs of each slot that had
    tokens, an empty slot's as they were. Rows past the live tokens hold anything.

    The BODY: every row's taps are the packed inputs shifted down by ``lag`` rows
    (zeros shifted in), right wherever the row has ``lag`` neighbours of its own
    segment in front of it. The HEADS: the first K - 1 rows of a segment, (K-1,
    S, Di) of them, see [the slot's tail ; the segment's first inputs] instead:
    head i of a slot takes input ``lag`` back from that window's entry
    K - 1 + i - lag, by the same ``_taps``, and is written over the body's row
    ``q_starts + i`` where ``i < q_lens`` (else to row T, which the scatter
    drops). The window's entries and the new tails are handled a (S, Di) slab at
    a time, the way the store lays them out (lag-major): no (T, Di) array of
    remembered inputs, no gather but of the 2 x (K-1) x S packed rows the heads
    and the new tails read. The module docstring has the invariant."""
    with jax.named_scope("ssm.conv"):
        del seg
        T, K, Di = u.shape[0], w.shape[0], u.shape[1]
        S, n = tails.shape[0], q_lens
        tails0 = jnp.where(fresh[:, None, None], 0.0, tails)
        old = [tails0[:, m] for m in range(K - 1)]                          # (S, Di) each, oldest first
        lead = jnp.minimum(n[0], 0).astype(jnp.float32) if b is None else b  # no bias: a zero no compiler folds
        uf = u.astype(jnp.float32)
        c = _taps(lambda lag: jnp.pad(uf[:T - lag], ((lag, 0), (0, 0))), w, lead, act)
        j = jnp.arange(K - 1)[:, None]
        rows = q_starts[None, :] + j                                        # (K-1, S): head i of slot s is packed row rows[i, s]

        def packed(at):   # (R, S) packed rows -> their inputs (R, S, Di); one off the array reads a neighbour, unused
            return u[jnp.clip(at, 0, T - 1).reshape(-1)].astype(jnp.float32).reshape(*at.shape, Di)

        taken = packed(jnp.concatenate([rows, rows + n[None, :] - (K - 1)]))   # the segments' first K - 1 rows, and their last
        first, last = taken[:K - 1], taken[K - 1:]
        window = old + [first[i] for i in range(K - 1)]                     # a slot's tail, then its first inputs
        heads = jnp.stack([_taps(lambda lag: window[K - 1 + i - lag], w, lead, act) for i in range(K - 1)])
        c = c.at[jnp.where(j < n[None, :], rows, T).reshape(-1)].set(heads.reshape(-1, Di), mode="drop")
        # New tail entry m of a slot is its input at segment index n - (K-1) + m; before the segment, tail entry m + n.
        new = []
        for m in range(K - 1):
            kept = old[m]
            for k in range(1, K - 1 - m):
                kept = jnp.where((n == k)[:, None], old[m + k], kept)
            new.append(jnp.where((n >= K - 1 - m)[:, None], last[m], kept))
        return c, jnp.where((n > 0)[:, None, None], jnp.stack(new, axis=1), tails)


def scan_ragged(states, delta, c, B, C, A, D, seg, positions, n_tokens):
    """The selective scan over packed tokens, one after another: token t
    reads the state its slot holds (zero where its position is 0), advances
    it and puts it back, so a segment's tokens chain through the store and
    the store ends with every slot's last state. states (S, Di, N) float32;
    delta, c (T, Di); B, C (T, N); positions (T,); n_tokens: how many
    packed tokens are live (they are packed from 0). Returns y (T, Di) and
    the new states."""
    with jax.named_scope("ssm.scan"):
        slot, _local, _live = seg
        S = states.shape[0]

        def step(t, carry):
            states, y = carry
            s = jnp.minimum(slot[t], S - 1)        # every token the loop visits is live
            prev = jnp.where(positions[t] == 0, 0.0, jax.lax.dynamic_index_in_dim(states, s, 0, keepdims=False))
            new = jnp.exp(delta[t][:, None] * A) * prev + (delta[t] * c[t])[:, None] * B[t][None, :]
            y = jax.lax.dynamic_update_index_in_dim(y, new @ C[t] + D * c[t], t, 0)
            return jax.lax.dynamic_update_index_in_dim(states, new, s, 0), y

        states, y = jax.lax.fori_loop(0, n_tokens, step, (states, jnp.zeros_like(c)))
        return y, states
