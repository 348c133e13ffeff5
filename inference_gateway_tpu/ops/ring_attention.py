"""Ring attention: sequence-parallel exact attention for long context.

Long-context prefill path (SURVEY.md §2.4 SP/CP row): the sequence is
sharded over the mesh's ``sp`` axis; each device holds a (B, T/N, H, D)
block of q/k/v. N ring steps rotate the KV blocks around the ``sp`` axis
with ``lax.ppermute`` (XLA lowers it to ICI neighbour transfers) while a
flash-style (m, l, acc) accumulator folds each visiting block into the
local queries — exact attention, O(T/N) memory per device, compute
overlapped with the rotation by XLA's scheduler.

Causality is enforced by *global* positions so the result is identical
to dense causal attention over the gathered sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, kv_pos, lengths, causal):
    """Scores for one (local q, visiting kv) block pair.

    q: (B, Tq, Hq, D); k/v: (B, Tk, Hkv, D); q_pos: (Tq,); kv_pos: (Tk,);
    lengths: (B,). Returns (scores_max, exp_scores@v, exp_row_sums).
    """
    B, Tq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Tq, Hkv, G, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32)
    scores = scores * (D ** -0.5)  # (B, Hkv, G, Tq, Tk)
    mask = kv_pos[None, :] < lengths[:, None]  # (B, Tk)
    if causal:
        mask = mask[:, None, :] & (kv_pos[None, None, :] <= q_pos[None, :, None])  # (B, Tq, Tk)
        mask = mask[:, None, None, :, :]
    else:
        mask = mask[:, None, None, None, :]
    return jnp.where(mask, scores, NEG_INF)


def make_ring_attention(mesh: Mesh, axis: str = "sp", causal: bool = True):
    """Build a ring-attention callable for sequence shards on ``axis``.

    Input/output: (B, T_local, H, D) shards; ``lengths`` (B,) are global
    valid lengths. All arrays except lengths are sequence-sharded.
    """
    n = mesh.shape[axis]

    def local_fn(q, k, v, lengths):
        B, Tq, Hq, D = q.shape
        my = jax.lax.axis_index(axis)
        q_pos = my * Tq + jnp.arange(Tq)

        def step(carry, i):
            k_blk, v_blk, m, l, acc = carry
            src = jax.lax.rem(my - i + n, n)  # who produced this block
            kv_pos = src * Tq + jnp.arange(Tq)
            scores = _block_attend(q, k_blk, v_blk, q_pos, kv_pos, lengths, causal)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            Hkv, G = k_blk.shape[2], Hq // k_blk.shape[2]
            pv = jnp.einsum("bkgts,bskd->bkgtd", p.astype(v_blk.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * alpha + pv.astype(jnp.float32)
            # Rotate kv to the next device on the ring.
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_nxt = jax.lax.ppermute(k_blk, axis, perm)
            v_nxt = jax.lax.ppermute(v_blk, axis, perm)
            return (k_nxt, v_nxt, m_new, l_new, acc_new), None

        Hkv = k.shape[2]
        G = Hq // Hkv
        # Mark the fresh accumulators as device-varying over the ring axis
        # so the scan carry type stays stable (shard_map vma semantics).
        def varying(x):
            return jax.lax.pcast(x, (axis,), to="varying")

        m0 = varying(jnp.full((B, Hkv, G, Tq, 1), NEG_INF, jnp.float32))
        l0 = varying(jnp.zeros((B, Hkv, G, Tq, 1), jnp.float32))
        acc0 = varying(jnp.zeros((B, Hkv, G, Tq, D), jnp.float32))
        (k, v, m, l, acc), _ = jax.lax.scan(step, (k, v, m0, l0, acc0), jnp.arange(n))
        out = acc / jnp.maximum(l, 1e-20)  # (B, Hkv, G, Tq, D)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, Hq, D).astype(q.dtype)

    seq_spec = P(None, axis, None, None)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P(None)),
        out_specs=seq_spec,
    )
