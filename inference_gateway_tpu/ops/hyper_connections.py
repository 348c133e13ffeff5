"""Manifold-constrained hyper-connections (arXiv:2512.24880; ISSUE 47): the
residual path as n streams a token, X (n, C) a token. A block F reads one mix of
them and its output is written back into all of them beside a mix of the
streams among themselves:

    x~     = RMSNorm(vec(X))                          (no weight; over n * C)
    H_pre  = sigmoid(a_pre x~ phi_pre + b_pre)               (n,)
    H_post = 2 sigmoid(a_post x~ phi_post + b_post)          (n,)
    H_res  = Sinkhorn(exp(clip(a_res mat(x~ phi_res) + b_res)))   (n, n), doubly stochastic
    u      = H_pre X                                         (C,)
    X'     = H_res X + H_post^T F(norm(u))

``phi`` holds the three projections side by side, (n * C, 2n + n * n);
``alpha`` the three scalars; ``bias`` the 2n + n * n offsets. The maps and the
Sinkhorn iterations are float32 (the projection's operands too: a TPU's
default product of float32 operands is one bfloat16 pass); the streams keep
the activations' dtype. The maps of a step's tokens lie with the TOKENS in the
lanes, (n, n, N): sixteen numbers a token are a sliver of a lane tile. The
streams of N tokens lie STREAM-MAJOR, (n, N, C), each stream a whole (N, C)
array: token-major (N, n, C) puts n = 4 on the sublane grid, a quarter of a
bfloat16 tile, and the read-in and write-back then run at a fifth of the
chip's bandwidth (0.11 ms for 15.6 MB; my chip runs, PR 47).

On a TPU the Sinkhorn projection is a Pallas kernel (``mhc_sinkhorn_kernel``),
for ONE reason: what a token's H_res comes to must not depend on where the
token sits in the step's packed batch, or a prompt adopted from the prefix
cache answers in another last bit than it did when computed whole (the
benchmark sends a probe twice and compares the floats). The compiler's own
fusion of the forty normalisations gave a token at packed position 0 and the
same token at 224 results 1.8e-7 apart, from bit-equal logits (my chip runs,
PR 47); in the kernel every tile of tokens runs one instruction sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from inference_gateway_tpu.utils.platform import on_tpu

SINKHORN_TILE = 1024  # tokens a row of the kernel's block: one (8, 128) float32 tile an entry of the matrix


def sinkhorn(m: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """m (n, n, ...) positive -> doubly stochastic over its first two axes:
    ``iters`` times rows (each i's sum over j) then columns, each division by
    (sum + eps)."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def _sinkhorn_kernel(logits_ref, out_ref, *, n: int, iters: int, eps: float):
    """exp, then ``sinkhorn``, entry by entry: logits_ref (n * n, tiles, 128),
    an entry of the matrix a row, a token a position of the (tiles, 128) tile.
    Sums in one fixed order; nothing crosses a lane or a sublane."""
    m = [[jnp.exp(logits_ref[i * n + j]) for j in range(n)] for i in range(n)]
    total = lambda xs: functools.reduce(lambda a, b: a + b, xs)
    for _ in range(iters):
        for i in range(n):
            s = total(m[i]) + eps
            m[i] = [x / s for x in m[i]]
        for j in range(n):
            s = total([m[i][j] for i in range(n)]) + eps
            for i in range(n):
                m[i][j] = m[i][j] / s
    for i in range(n):
        for j in range(n):
            out_ref[i * n + j] = m[i][j]


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "interpret"))
def sinkhorn_exp_tpu(logits: jnp.ndarray, *, n: int, iters: int, eps: float, interpret: bool = False) -> jnp.ndarray:
    """``sinkhorn(exp(logits))`` of logits (n * n, N), row-major, by the kernel:
    (n, n, N). Padding tokens (logits 0) project the all-ones matrix."""
    N = logits.shape[1]
    padded = -(-N // SINKHORN_TILE) * SINKHORN_TILE
    x = jnp.pad(logits, ((0, 0), (0, padded - N))).reshape(n * n, padded // 128, 128)
    block = pl.BlockSpec((n * n, SINKHORN_TILE // 128, 128), lambda t: (0, t, 0))
    out = pl.pallas_call(
        functools.partial(_sinkhorn_kernel, n=n, iters=iters, eps=eps),
        grid=(padded // SINKHORN_TILE,), in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32), interpret=interpret, name="mhc_sinkhorn_kernel")(x)
    return out.reshape(n * n, padded)[:, :N].reshape(n, n, N)


def maps(X: jnp.ndarray, phi, alpha, bias, *, iters: int, eps: float, clamp: tuple, norm_eps: float):
    """The three maps of N tokens' streams X (n, N, C): H_pre (n, N), H_post
    (n, N), H_res (n, n, N), float32."""
    n, N, C = X.shape
    with jax.named_scope("mhc.maps"):
        xf = X.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.sum(xf * xf, axis=(0, 2)) / (n * C) + norm_eps)
        # vec(X) phi, a stream's rows of phi at a time: (2n + n*n, N)
        proj = jnp.einsum("inc,icm->mn", xf, phi.astype(jnp.float32).reshape(n, C, -1),
                          precision=jax.lax.Precision.HIGHEST) * inv
        a, b = alpha.astype(jnp.float32), bias.astype(jnp.float32)[:, None]
        pre = jax.nn.sigmoid(a[0] * proj[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * proj[n:2 * n] + b[n:2 * n])
        logits = jnp.clip(a[2] * proj[2 * n:] + b[2 * n:], clamp[0], clamp[1])
    with jax.named_scope("mhc.sinkhorn"):
        if on_tpu():
            return pre, post, sinkhorn_exp_tpu(logits, n=n, iters=iters, eps=eps)
        return pre, post, sinkhorn(jnp.exp(logits).reshape(n, n, N), iters, eps)


def read(X: jnp.ndarray, pre: jnp.ndarray) -> jnp.ndarray:
    """u = H_pre X: X (n, N, C), pre (n, N) -> (N, C)."""
    with jax.named_scope("mhc.read"):
        return sum(pre[i][:, None] * X[i].astype(jnp.float32) for i in range(X.shape[0])).astype(X.dtype)


def write(X: jnp.ndarray, y: jnp.ndarray, post: jnp.ndarray, res: jnp.ndarray) -> jnp.ndarray:
    """X' = H_res X + H_post^T y: y (N, C) the block's output."""
    with jax.named_scope("mhc.write"):
        n = X.shape[0]
        Xf, yf = X.astype(jnp.float32), y.astype(jnp.float32)
        return jnp.stack([sum(res[i, j][:, None] * Xf[j] for j in range(n)) + post[i][:, None] * yf
                          for i in range(n)]).astype(X.dtype)
