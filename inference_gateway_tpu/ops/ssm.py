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


def conv_ragged(u, tails, w, b, seg, fresh, q_starts, q_lens, act=jax.nn.silu):
    """The convolution over packed tokens. u (T, Di); tails (S, K-1, Di);
    ``seg`` = token_segments(...); fresh (S,) slots whose segment starts at
    position 0 (their tail counts as zero); b (Di,) or None. Returns c (T, Di)
    float32 and the new tails: the last K - 1 inputs of each slot that had tokens."""
    with jax.named_scope("ssm.conv"):
        slot, local, live = seg
        T, K = u.shape[0], w.shape[0]
        S = tails.shape[0]
        u = u.astype(jnp.float32)
        tails0 = jnp.where(fresh[:, None, None], 0.0, tails)
        row = jnp.minimum(slot, S - 1)
        acc = w[K - 1] * u if b is None else b + w[K - 1] * u
        for lag in range(1, K):
            # The input ``lag`` tokens back: a packed neighbour while that is
            # still inside the segment, else what the slot's tail remembers.
            back = jnp.roll(u, lag, axis=0)
            remembered = tails0[row, jnp.clip(K - 1 + local - lag, 0, K - 2)]
            acc = acc + w[K - 1 - lag] * jnp.where((local >= lag)[:, None], back, remembered)
        c = acc if act is None else act(acc)
        # New tail entry m of a slot is its input at segment index n - (K-1) + m.
        want = q_lens[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]      # (S, K-1)
        from_u = u[jnp.clip(q_starts[:, None] + want, 0, T - 1)]          # (S, K-1, Di)
        from_tail = jnp.take_along_axis(tails0, jnp.clip(want + K - 1, 0, K - 2)[:, :, None], axis=1)
        new = jnp.where((want >= 0)[:, :, None], from_u, from_tail)
        return c, jnp.where((q_lens > 0)[:, None, None], new, tails)


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
