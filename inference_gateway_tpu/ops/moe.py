"""Mixture-of-experts ops: top-k routing and a dropless expert layer.

``moe_dropless`` is the serving path (ISSUE 28): every (token, expert)
assignment is computed and none is dropped. The assignments are sorted
by expert, each expert multiplies its own contiguous group of rows
(``grouped_matmul``), and the results are gathered back per token and
summed under the routing weights. There is no ``(N, E, C)`` dispatch
tensor and no capacity. One algorithm serves decode (a few rows an
expert) and the mixed step (tens of rows an expert); only the row tile
follows the row count.

``grouped_matmul`` on one TPU device is a Pallas kernel over the WHOLE
stacked expert leaf ``(L, E, K, N)``: the layer index and each visit's
expert ride in scalar prefetch, so no per-layer slice of the stack is
ever materialised, an int8 expert is read from HBM once, at int8, and
dequantised in VMEM where it is multiplied (``jax.lax.ragged_dot`` given
``q.astype(bf16)`` writes a bf16 copy of all E experts per call: compiled
for a described v5e, PR 28). Off the TPU and for int4 the twin is
``jax.lax.ragged_dot`` on the dequantised layer, the same sums.

On a mesh everything after the router runs under ``shard_map`` (a Mosaic
kernel cannot be partitioned automatically): tokens and routing are
replicated, each ``ep`` shard multiplies the rows of its own experts (a
contiguous span of the sorted rows), each ``tp`` shard its columns of the
gate and up matrices and its rows of the down matrix, every shard combines
its own part per token, and one ``psum`` of the (N, H) result over ``ep``
and ``tp`` finishes the layer: no all-to-all, no expert weight moves.

``moe_dense`` runs every expert on every token: exact, O(E) compute,
the tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from inference_gateway_tpu.ops.quant import Q4Tensor, QTensor, _dequant4
from inference_gateway_tpu.utils.platform import on_tpu

ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def router_topk(logits: jnp.ndarray, top_k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing with renormalized softmax weights.

    logits: (N, E) → (weights (N, k), idx (N, k)). Matches Mixtral: softmax
    over the top-k logits only.
    """
    vals, idx = jax.lax.top_k(logits, top_k)  # (N, k)
    weights = jax.nn.softmax(vals.astype(jnp.float32), axis=-1)
    return weights, idx


def router_sigmoid(logits: jnp.ndarray, top_k: int, *, bias: jnp.ndarray, scale: float
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing over sigmoid scores with a selection bias (ISSUE 41).

    logits: (N, E) float32; bias: (E,). The chosen are the ``top_k`` largest
    of ``sigmoid(logits) + bias``; their weights are the scores WITHOUT the
    bias, normalised over the chosen and multiplied by ``scale``. The bias
    decides who is chosen and never what a chosen expert weighs.
    """
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)  # (N, k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    return weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20) * scale, idx


def moe_dense(x: jnp.ndarray, router_logits: jnp.ndarray, top_k: int, expert_fn,
              route=router_topk) -> jnp.ndarray:
    """Exact MoE: run all experts, combine by routing weight.

    x: (N, H); router_logits: (N, E); expert_fn: (E, N, H) -> (E, N, H);
    ``route``: the routing seam of ``moe_dropless``.
    """
    N, H = x.shape
    E = router_logits.shape[-1]
    weights, idx = route(router_logits, top_k)  # (N, k)
    # Scatter top-k weights into a dense (N, E) combine matrix.
    combine = jnp.zeros((N, E), jnp.float32)
    combine = combine.at[jnp.arange(N)[:, None], idx].add(weights)
    expert_out = expert_fn(jnp.broadcast_to(x, (E, N, H)))  # (E, N, H)
    return jnp.einsum("ne,enh->nh", combine, expert_out.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Grouped matmul over a stacked expert leaf
# ---------------------------------------------------------------------------

def _row_tile(m: int) -> int:
    """Rows a visit multiplies: small where an expert sees a few rows
    (decode: a visit's MXU passes follow the tile, its weight read does
    not), the MXU's height where it sees tens."""
    return 32 if m <= 512 else 128


def _col_tile(n: int) -> int:
    for c in (512, 384, 256, 128):
        if n % c == 0:
            return c
    return n


def _visits(group_sizes: jnp.ndarray, start, m: int, tm: int):
    """Which (expert, row tile) pairs hold work, in row order: an expert
    with rows visits every tile its span touches, so a tile shared by
    several experts is visited once by each, back to back. The first
    group's rows begin at row ``start`` (an ``ep`` shard's experts own a
    span in the middle of the sorted rows). Returns (offsets (E+1,),
    expert of visit (V,), tile of visit (V,), visits (1,)) with
    V = m/tm + E - 1, the most there can be; the tail repeats the last
    real visit (no new block is fetched) and is skipped."""
    E = group_sizes.shape[0]
    tiles = m // tm
    V = tiles + E - 1
    start = jnp.asarray(start, jnp.int32)
    ends = start + jnp.cumsum(group_sizes).astype(jnp.int32)
    starts = ends - group_sizes
    offsets = jnp.concatenate([start[None], ends])
    first = starts // tm
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(n_tiles)
    num = visit_end[-1]
    v = jnp.arange(V)
    gid = jnp.searchsorted(visit_end, v, side="right").astype(jnp.int32)
    gid = jnp.minimum(gid, E - 1)
    tid = first[gid] + (v - (visit_end[gid] - n_tiles[gid]))
    last = jnp.maximum(num - 1, 0)
    gid = jnp.where(v < num, gid, gid[last])
    tid = jnp.where(v < num, tid, tid[last])
    tid = jnp.clip(tid, 0, tiles - 1).astype(jnp.int32)
    return offsets, gid, tid, num.astype(jnp.int32)[None]


def _gmm_kernel(off_ref, gid_ref, tid_ref, num_ref, li_ref, x_ref, *refs, tm, n_w, scaled, act):
    """One visit: the row tile times expert ``gid[v]``'s column block,
    kept for the rows of that expert's span. ``n_w`` 2 is the gated pair
    (act(x Wg) * x Wu) in one pass over x."""
    from jax.experimental import pallas as pl

    del li_ref  # the index maps read it
    o_ref = refs[-1]
    w_refs = refs[:-1]
    v = pl.program_id(1)

    @pl.when(v < num_ref[0])
    def _():
        g = gid_ref[v]
        rows = tid_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
        x = x_ref[...]

        def mul(i):
            w = w_refs[i * (2 if scaled else 1)][...]
            y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
            if scaled:
                y = y * w_refs[i * 2 + 1][...]
            return y

        y = mul(0)
        if n_w == 2:
            y = ACTIVATIONS[act](y) * mul(1)
        # A tile's first visit finds whatever the buffer held: rows of no
        # expert (the padding past the last span) must read zero, not that.
        fresh = (v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != tid_ref[v])
        prev = jnp.where(fresh, jnp.zeros_like(o_ref), o_ref[...])
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), prev)


@functools.partial(jax.jit, static_argnames=("act", "out_dtype", "interpret"))
def _gmm_tpu(x, ws, layer, group_sizes, start, act, out_dtype, interpret=False):
    """x: (M, K) rows sorted by expert, M a multiple of its row tile, the
    first group's rows from row ``start``; ws: one or two stacked leaves
    (L, E, K, N), arrays or QTensors."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    scaled = isinstance(ws[0], QTensor)
    N = ws[0].shape[-1]
    tm, tn = _row_tile(M), _col_tile(N)
    offsets, gid, tid, num = _visits(group_sizes, start, M, tm)
    V = gid.shape[0]

    def w_index(n, v, off, gid, tid, num, li):
        return li[0], gid[v], 0, n

    operands, in_specs = [x], [pl.BlockSpec((tm, K), lambda n, v, off, gid, tid, num, li: (tid[v], 0))]
    for w in ws:
        if scaled:
            operands += [w.q, w.scale]
            in_specs += [pl.BlockSpec((None, None, K, tn), w_index),
                         pl.BlockSpec((None, None, 1, tn), w_index)]
        else:
            operands.append(w)
            in_specs.append(pl.BlockSpec((None, None, K, tn), w_index))
    kernel = functools.partial(_gmm_kernel, tm=tm, n_w=len(ws), scaled=scaled, act=act)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(N // tn, V), in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, off, gid, tid, num, li: (tid[v], n))),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=64 << 20),
        name="moe_gate_up_gmm" if len(ws) == 2 else "moe_down_gmm",
        interpret=interpret,
    )(offsets, gid, tid, num, jnp.asarray(layer, jnp.int32).reshape(1), *operands)


def _layer_weight(w, layer, dtype):
    """Layer ``layer`` of a stacked leaf as (matrix (E, K, N) in ``dtype``,
    per-channel scale (E, N) float32 or None)."""
    if isinstance(w, QTensor):
        return w.q[layer].astype(dtype), w.scale[layer][:, 0, :]
    if isinstance(w, Q4Tensor):
        return _dequant4(Q4Tensor(w.q[layer], w.scale[layer]), dtype), None
    return w[layer].astype(dtype), None


def _gmm_twin(x, ws, layer, group_sizes, start, row_expert, act, out_dtype):
    # ragged_dot's groups begin at row 0: the rows before ``start`` ride
    # with the first group, and the caller keeps no row outside the span.
    sizes = group_sizes.at[0].add(start)

    def mul(w):
        mat, scale = _layer_weight(w, layer, x.dtype)
        y = jax.lax.ragged_dot(x, mat, sizes, preferred_element_type=jnp.float32)
        return y if scale is None else y * scale[row_expert]

    y = mul(ws[0])
    if len(ws) == 2:
        y = ACTIVATIONS[act](y) * mul(ws[1])
    return y.astype(out_dtype)


def grouped_matmul(x, ws, layer, group_sizes, start, row_expert, *, act="silu", out_dtype=None,
                   kernel=None):
    """Rows of ``x`` (sorted by expert; from row ``start`` on,
    ``group_sizes`` rows each; ``row_expert`` names each row's) times
    their expert's matrix of layer ``layer`` of the stacked leaf. Two
    leaves are the gated pair: act(x Wg) * (x Wu). Rows outside the groups
    come back zero, unwritten or another expert's: the caller masks them.
    ``kernel`` None: the Pallas kernel on a TPU, the twin elsewhere, for
    int4, and for a ``tp`` shard whose columns are not whole lanes (768
    over tp = 4: the compiler re-lays the WHOLE stack out per call for the
    kernel, 1.3 GB; the twin copies one layer). Tests force the kernel,
    interpreted off the TPU."""
    out_dtype = out_dtype or x.dtype
    if kernel is None:
        kernel = on_tpu() and not isinstance(ws[0], Q4Tensor) and ws[0].shape[-1] % 128 == 0
    if kernel:
        return _gmm_tpu(x, tuple(ws), layer, group_sizes, start, act, out_dtype, interpret=not on_tpu())
    return _gmm_twin(x, ws, layer, group_sizes, start, row_expert, act, out_dtype)


# ---------------------------------------------------------------------------
# The dropless layer
# ---------------------------------------------------------------------------

def _experts_of_shard(x, weights, expert, wg, wu, wd, layer, *, act, kernel, ep, tp):
    """Everything after the router, for the experts (and, under ``tp``,
    the columns) this shard holds: sort, the two grouped matmuls, the
    combine per token. x (N, H), weights (N, k) and expert (N*k,) are
    whole on every shard; the stacks are the shard's own
    (L, E/ep, K, N). Returns (this shard's part of the output (N, H)
    float32, rows each of ALL experts multiplied (E,))."""
    N, H = x.shape
    top_k = weights.shape[1]
    M = N * top_k
    e_local = jax.tree.leaves(wg)[0].shape[1]
    E = e_local * (jax.lax.axis_size(ep) if ep else 1)
    with jax.named_scope("moe.dispatch"):
        order = jnp.argsort(expert, stable=True)  # E (no expert) sorts last: no group
        group_sizes = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]
        first = jax.lax.axis_index(ep) * e_local if ep else 0
        mine = jax.lax.dynamic_slice(group_sizes, (first,), (e_local,))
        start = jnp.sum(jnp.where(jnp.arange(E) < first, group_sizes, 0))
        row_expert = jnp.clip(expert[order] - first, 0, e_local - 1)
        pad = -M % _row_tile(M)
        rows = x[order // top_k]
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
            row_expert = jnp.pad(row_expert, (0, pad), constant_values=e_local - 1)
    with jax.named_scope("moe.experts"):
        h = grouped_matmul(rows, (wg, wu), layer, mine, start, row_expert, act=act, kernel=kernel)
        # tp shards hold partial sums of the down matrix: kept float32 until summed.
        y = grouped_matmul(h, (wd,), layer, mine, start, row_expert, kernel=kernel,
                           out_dtype=jnp.float32 if tp else None)
    with jax.named_scope("moe.combine"):
        at = jnp.arange(M + pad)
        live = (at >= start) & (at < start + jnp.sum(mine))
        y = jnp.where(live[:, None], y.astype(jnp.float32), 0.0)
        back = jnp.zeros((M,), jnp.int32).at[order].set(jnp.arange(M, dtype=jnp.int32))
        per_choice = y[back].reshape(N, top_k, H)
        out = jnp.einsum("nk,nkh->nh", weights, per_choice)
        axes = tuple(a for a in (ep, tp) if a)
        if axes:
            out = jax.lax.psum(out, axes)
    return out, group_sizes


def _stack_spec(w, ep, k_axis, n_axis):
    """PartitionSpecs of a stacked expert leaf (L, E, K, N) as
    models/mixtral.param_specs and parallel/sharding.quantized_specs lay
    it out: experts over ``ep``; int8 scales (L, E, 1, N), int4 scales
    (L, E, K/group, 1, N)."""
    from jax.sharding import PartitionSpec as P

    if isinstance(w, QTensor):
        return QTensor(P(None, ep, k_axis, n_axis), P(None, ep, None, n_axis))
    if isinstance(w, Q4Tensor):
        return Q4Tensor(P(None, ep, k_axis, n_axis), P(None, ep, k_axis, None, n_axis))
    return P(None, ep, k_axis, n_axis)


def moe_dropless(
    x: jnp.ndarray,  # (N, H) the experts' input
    router_logits: jnp.ndarray,  # (N, E) float32
    top_k: int,
    wg, wu, wd,  # stacked leaves (L, E, H, I) x2, (L, E, I, H)
    layer,  # int32 scalar: which layer of the stacks
    *,
    act: str = "silu",
    valid: jnp.ndarray | None = None,  # (N,) bool: rows that carry a token
    mesh=None,  # the mesh the stacks are sharded over (experts on "ep", each split on "tp")
    kernel: bool | None = None,  # None: the Pallas kernel on a TPU
    route=router_topk,  # (logits, top_k) -> (weights (N, k) float32, idx (N, k)): who is chosen, and what each weighs
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Every chosen (token, expert) pair, computed once. Returns the
    layer's output (N, H) and how many rows each expert multiplied (E,):
    the program's own count, from which ``experts_read`` and the
    ``expert_tokens_*`` fields of the timeline are made. Rows with
    ``valid`` false choose no expert, are multiplied by none and come
    back zero."""
    E = router_logits.shape[-1]
    with jax.named_scope("moe.router"):
        weights, idx = route(router_logits, top_k)  # (N, k)
        expert = idx.reshape(-1).astype(jnp.int32)  # (N*k,) token-major
        if valid is not None:
            expert = jnp.where(jnp.repeat(valid, top_k), expert, E)
    if mesh is None:
        out, rows = _experts_of_shard(x, weights, expert, wg, wu, wd, layer,
                                      act=act, kernel=kernel, ep=None, tp=None)
    else:
        from jax.sharding import PartitionSpec as P

        ep, tp = (a if mesh.shape.get(a, 1) > 1 else None for a in ("ep", "tp"))
        out, rows = jax.shard_map(
            functools.partial(_experts_of_shard, act=act, kernel=kernel, ep=ep, tp=tp),
            mesh=mesh,
            in_specs=(P(), P(), P(), _stack_spec(wg, ep, None, tp), _stack_spec(wu, ep, None, tp),
                      _stack_spec(wd, ep, tp, None), P()),
            out_specs=(P(), P()), check_vma=False,
        )(x, weights, expert, wg, wu, wd, jnp.asarray(layer, jnp.int32))
    return out.astype(x.dtype), rows
