"""Mixture-of-experts ops: top-k routing and a dropless expert layer.

``moe_dropless`` is the serving path (ISSUE 28): every (token, expert)
assignment is computed and none is dropped. The assignments are sorted
by expert, each expert's group begins at a granule boundary (``dispatch``:
at most a granule less one padded rows an expert), each expert multiplies
its own group (``grouped_matmul``), and the results are gathered back per
token and summed under the routing weights. There is no ``(N, E, C)``
dispatch tensor and no capacity. One algorithm serves decode (a few rows
an expert) and the mixed step (tens of rows an expert); only the rows a
visit multiplies follow the rows an expert holds (``visit_plan``).

``grouped_matmul`` on one TPU device is a Pallas kernel over the WHOLE
stacked expert leaf ``(L, E, K, N)``: the layer index and each visit's
expert ride in scalar prefetch, so no per-layer slice of the stack is
ever materialised, an int8 expert is read from HBM once, at int8, and
dequantised in VMEM where it is multiplied, once unless it holds more
rows than a visit (ISSUE 42: a visit is ONE expert's rows, its column
block as wide as ``VISIT_BYTES`` allows) (``jax.lax.ragged_dot`` given
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
from typing import NamedTuple

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


def router_sigmoid(logits: jnp.ndarray, top_k: int, *, bias: jnp.ndarray, scale: float,
                   eps: float = 1e-20) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing over sigmoid scores with a selection bias (ISSUE 41).

    logits: (N, E) float32; bias: (E,). The chosen are the ``top_k`` largest
    of ``sigmoid(logits) + bias``; their weights are the scores WITHOUT the
    bias, normalised over the chosen (their sum + ``eps``: 1e-20 in Trinity's
    modelling code, 1e-6 in LFM2's) and multiplied by ``scale``. The bias
    decides who is chosen and never what a chosen expert weighs.
    """
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)  # (N, k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    return weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps) * scale, idx


def moe_dense(x: jnp.ndarray, router_logits: jnp.ndarray, top_k: int, expert_fn,
              route=router_topk, first: int = 0, held: int | None = None) -> jnp.ndarray:
    """Exact MoE: run all experts, combine by routing weight.

    x: (N, H); router_logits: (N, E); expert_fn: (E', N, H) -> (E', N, H), the
    E' = ``held`` experts from expert ``first`` on (all E but for a chip's
    share, ``moe_dropless``); ``route``: the routing seam of ``moe_dropless``.
    """
    N, H = x.shape
    E = router_logits.shape[-1]
    weights, idx = route(router_logits, top_k)  # (N, k)
    # Scatter top-k weights into a dense (N, E) combine matrix.
    combine = jnp.zeros((N, E), jnp.float32)
    combine = combine.at[jnp.arange(N)[:, None], idx].add(weights)
    held = E if held is None else held
    expert_out = expert_fn(jnp.broadcast_to(x, (held, N, H)))  # (E', N, H)
    return jnp.einsum("ne,enh->nh", combine[:, first:first + held], expert_out.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Grouped matmul over a stacked expert leaf
# ---------------------------------------------------------------------------

# A visit's weight operands as they are stored (both matrices of the gated
# pair), one buffer: the column block is the widest that fits. The pipeline
# holds two such buffers and the body their dequantised copies, beside the
# row tile and the product: inside ``vmem_limit_bytes`` with room to spare.
VISIT_BYTES = 4 << 20
# (rows an expert up to, granule, granules a visit), by what each cost on the
# chip at the cells' shapes (``PERF.md`` section 5, PR 42): a visit's time is
# its weight block's way through the MXU and grows ~7% from 64 rows to 128,
# so it takes the fewest rows that hold an expert's share in one visit.
VISIT_ROWS = ((16, 16, 2), (64, 16, 4), (None, 32, 4))


class VisitPlan(NamedTuple):
    """How the kernels walk a layer's rows, from its static shapes alone."""
    granule: int  # every expert's rows lie from a multiple of this many rows on
    chunks: int  # a visit multiplies up to this many granules of ONE expert
    rows: int  # rows of the layout: each expert with rows pads its group to the granule
    visits: int  # the most visits there can be (the grid)


def visit_plan(m: int, e: int, e_local: int | None = None) -> VisitPlan:
    """``m`` rows over ``e`` experts, ``e_local`` of them on this shard.
    Every expert's rows begin at a granule boundary, so that an expert is
    visited once unless it holds more rows than a visit (no visit is shared,
    none is masked), and the granule only bounds what the layout pads (at
    most ``e_local`` x (granule - 1) rows, which the row gather writes)."""
    g, c = next((g, c) for most, g, c in VISIT_ROWS if most is None or m <= most * e)
    holders = min(e if e_local is None else e_local, m)
    return VisitPlan(g, c, (m + holders * (g - 1)) // g * g, m // (g * c) + holders)


def visit_count(rows: jnp.ndarray, m: int) -> jnp.ndarray:
    """rows (..., E): the rows each expert holds of ``m`` in all -> the
    visits the kernel makes (its ``num``): one for every ``granule`` x
    ``chunks`` rows of an expert, begun or whole."""
    plan = visit_plan(m, rows.shape[-1])
    return jnp.sum(-(-rows // (plan.granule * plan.chunks)), axis=-1)


def _col_block(k: int, n: int, n_w: int, itemsize: int) -> int:
    """The widest divisor of ``n`` in whole lanes whose ``n_w`` (k, block)
    operands fit ``VISIT_BYTES``; ``n`` itself where no divisor is whole lanes."""
    if n % 128:
        return n
    return max([c for c in range(128, n + 1, 128) if n % c == 0 and n_w * k * c * itemsize <= VISIT_BYTES],
               default=128)


def _visits(sizes: jnp.ndarray, plan: VisitPlan):
    """The visits in row order, for groups of ``sizes`` rows laid out from
    granule boundaries. A visit is one expert and up to ``chunks``
    consecutive granules of its rows; an expert with no rows has none.
    Returns (expert of visit (V,), its first granule (V,), its granules
    (V,), the granule each of the ``chunks`` row operands holds (chunks,
    V), visits (1,)). A row operand a visit does not need keeps the
    granule it held, so no copy is made for it; the tail past ``num``
    repeats the last visit's blocks and has no granules."""
    g, c, _, V = plan
    experts = jnp.arange(sizes.shape[0], dtype=jnp.int32)
    held = (-(-sizes // g)).astype(jnp.int32)  # granules an expert holds
    n_visits = -(-held // c)
    visit_end = jnp.cumsum(n_visits)
    num = visit_end[-1]
    v = jnp.arange(V, dtype=jnp.int32)
    # (E, V), a visit's column true at its expert: every look-up below is a
    # masked sum over it (a gather of V elements costs the chip more than this).
    mine = (v[None, :] >= (visit_end - n_visits)[:, None]) & (v[None, :] < visit_end[:, None])

    def of_visit(a):
        return jnp.sum(jnp.where(mine, a[:, None], 0), axis=0)

    real = v < num
    nth = v - of_visit(visit_end - n_visits)
    gid = jnp.where(real, of_visit(experts), jnp.max(jnp.where(sizes > 0, experts, 0)))
    chunk0 = jnp.where(real, of_visit(jnp.cumsum(held) - held) + nth * c, 0)
    cnt = jnp.where(real, jnp.minimum(of_visit(held) - nth * c, c), 0)
    each = jnp.arange(c, dtype=jnp.int32)[:, None]
    holds = jax.lax.cummax(jnp.where(each < cnt[None, :], chunk0[None, :] + each, 0), axis=1)
    return gid, chunk0, cnt, holds, num.astype(jnp.int32)[None]


def _gmm_kernel(gid_ref, chunk0_ref, cnt_ref, holds_ref, num_ref, li_ref, *refs,
                g, c, tn, n_w, scaled, act):
    """One visit: up to ``c`` granules of ONE expert's rows times its column
    block, written to the rows' own place. The granules arrive as ``c`` row
    operands and leave by one copy each, started here and awaited by the
    next visit before it refills the buffer (the copy has that visit's
    products to finish in). ``n_w`` 2 is the gated pair (act(x Wg) * x Wu)
    in one pass over x."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del gid_ref, holds_ref, li_ref  # the index maps read them
    x_refs, w_refs = refs[:c], refs[c:-3]
    o_ref, buf, sem = refs[-3:]
    n, v = pl.program_id(0), pl.program_id(1)
    num = num_ref[0]

    def copy(i, granule):
        dst = o_ref.at[pl.ds(pl.multiple_of(granule * g, g), g), pl.ds(pl.multiple_of(n * tn, 128), tn)]
        return pltpu.make_async_copy(buf.at[pl.ds(i * g, g)], dst, sem.at[i])

    def retire(count):
        for i in range(c):
            pl.when(i < count)(lambda i=i: copy(i, 0).wait())

    before = jnp.where((v > 0) & (v <= num), cnt_ref[jnp.maximum(v - 1, 0)], 0)

    @pl.when(v < num)
    def _():
        x = jnp.concatenate([r[...] for r in x_refs], axis=0)

        def mul(i):
            w = w_refs[i * (2 if scaled else 1)][...]
            y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
            if scaled:
                y = y * w_refs[i * 2 + 1][...]
            return y

        y = mul(0)
        if n_w == 2:
            y = ACTIVATIONS[act](y) * mul(1)
        retire(before)
        buf[...] = y.astype(buf.dtype)
        for i in range(c):
            pl.when(i < cnt_ref[v])(lambda i=i: copy(i, chunk0_ref[v] + i).start())
        # the grid's last step is the last visit only where every step is one
        pl.when(v == pl.num_programs(1) - 1)(lambda: retire(cnt_ref[v]))

    pl.when(v == num)(lambda: retire(before))


@functools.partial(jax.jit, static_argnames=("plan", "act", "out_dtype", "interpret"))
def _gmm_tpu(x, ws, layer, sizes, plan, act, out_dtype, interpret=False):
    """x: (``plan.rows``, K), the groups of ``sizes`` rows from granule
    boundaries on; ws: one or two stacked leaves (L, E, K, N), arrays or
    QTensors. Rows of no group come back unwritten."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, c, rows, V = plan
    K = x.shape[1]
    scaled = isinstance(ws[0], QTensor)
    N = ws[0].shape[-1]
    stored = ws[0].q if scaled else ws[0]
    tn = _col_block(K, N, len(ws), stored.dtype.itemsize)

    def w_index(n, v, gid, chunk0, cnt, holds, num, li):
        return li[0], gid[v], 0, n

    operands = [x] * c
    in_specs = [pl.BlockSpec((g, K), lambda n, v, gid, chunk0, cnt, holds, num, li, i=i: (holds[i, v], 0))
                for i in range(c)]
    for w in ws:
        if scaled:
            operands += [w.q, w.scale]
            in_specs += [pl.BlockSpec((None, None, K, tn), w_index),
                         pl.BlockSpec((None, None, 1, tn), w_index)]
        else:
            operands.append(w)
            in_specs.append(pl.BlockSpec((None, None, K, tn), w_index))
    kernel = functools.partial(_gmm_kernel, g=g, c=c, tn=tn, n_w=len(ws), scaled=scaled, act=act)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(N // tn, V), in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
            scratch_shapes=[pltpu.VMEM((g * c, tn), out_dtype), pltpu.SemaphoreType.DMA((c,))]),
        out_shape=jax.ShapeDtypeStruct((rows, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=64 << 20),
        name="moe_gate_up_gmm" if len(ws) == 2 else "moe_down_gmm",
        interpret=interpret,
    )(*_visits(sizes, plan), jnp.asarray(layer, jnp.int32).reshape(1), *operands)


def _layer_weight(w, layer, dtype):
    """Layer ``layer`` of a stacked leaf as (matrix (E, K, N) in ``dtype``,
    per-channel scale (E, N) float32 or None)."""
    if isinstance(w, QTensor):
        return w.q[layer].astype(dtype), w.scale[layer][:, 0, :]
    if isinstance(w, Q4Tensor):
        return _dequant4(Q4Tensor(w.q[layer], w.scale[layer]), dtype), None
    return w[layer].astype(dtype), None


def _gmm_twin(x, ws, layer, sizes, plan, row_expert, act, out_dtype):
    # the groups as they lie: each padded to the granule, back to back from row 0
    padded = -(-sizes // plan.granule) * plan.granule

    def mul(w):
        mat, scale = _layer_weight(w, layer, x.dtype)
        y = jax.lax.ragged_dot(x, mat, padded, preferred_element_type=jnp.float32)
        return y if scale is None else y * scale[row_expert]

    y = mul(ws[0])
    if len(ws) == 2:
        y = ACTIVATIONS[act](y) * mul(ws[1])
    return y.astype(out_dtype)


def grouped_matmul(x, ws, layer, sizes, plan, row_expert, *, act="silu", out_dtype=None, kernel=None):
    """Rows of ``x`` (``plan.rows`` of them: expert after expert, ``sizes``
    rows each, every expert's from a granule boundary on; ``row_expert``
    names each row's) times their expert's matrix of layer ``layer`` of the
    stacked leaf. Two leaves are the gated pair: act(x Wg) * (x Wu). Rows
    of no expert come back unwritten or as some expert's product of
    whatever they held: the caller reads none of them.
    ``kernel`` None: the Pallas kernel on a TPU, the twin elsewhere, for
    int4, and for a ``tp`` shard whose columns are not whole lanes (768
    over tp = 4: the compiler re-lays the WHOLE stack out per call for the
    kernel, 1.3 GB; the twin copies one layer). Tests force the kernel,
    interpreted off the TPU."""
    out_dtype = out_dtype or x.dtype
    if kernel is None:
        kernel = on_tpu() and not isinstance(ws[0], Q4Tensor) and ws[0].shape[-1] % 128 == 0
    if kernel:
        return _gmm_tpu(x, tuple(ws), layer, sizes, plan, act, out_dtype, interpret=not on_tpu())
    return _gmm_twin(x, ws, layer, sizes, plan, row_expert, act, out_dtype)


# ---------------------------------------------------------------------------
# The dropless layer
# ---------------------------------------------------------------------------

class Layout(NamedTuple):
    """Where a shard's rows lie (``dispatch``)."""
    plan: VisitPlan
    token: jnp.ndarray  # (plan.rows,) the token whose row lies there; 0 where none does
    row_expert: jnp.ndarray  # (plan.rows,) the shard's expert whose group the row lies in
    back: jnp.ndarray  # (M,) where each (token, choice) lies; plan.rows where this shard holds no row of it
    sizes: jnp.ndarray  # (E',) rows each of the shard's experts holds
    all_sizes: jnp.ndarray  # (E,) rows each of ALL experts holds


def dispatch(expert, top_k: int, e: int, e_local: int | None = None, first=0) -> Layout:
    """Lay the rows of this shard's experts (``e_local`` of ``e``, from
    expert ``first`` on; all of them by default) out for the kernels:
    expert after expert, a token's row once for each of its choices, each
    expert's rows from a granule boundary on. ``expert`` (M,) is each
    (token, choice)'s expert, token-major; ``e`` means none. One sort lays
    them out: beside the (token, choice) pairs every expert brings the
    fillers that pad its group to the granule, and what the shard does not
    hold (another shard's, no expert's, a spare filler) sorts last; a second
    sort, by pair, says where each pair went."""
    M = expert.shape[0]
    e_local = e if e_local is None else e_local
    plan = visit_plan(M, e, e_local)
    g = plan.granule
    # counted by comparison, turned back by a second sort: a scatter of M
    # elements costs the chip five times either (``PERF.md`` section 5, PR 42)
    all_sizes = jnp.sum(expert[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32)
    sizes = jax.lax.dynamic_slice(all_sizes, (first,), (e_local,))
    local = expert - first
    held = (local >= 0) & (local < e_local)
    spare = jnp.arange(g - 1, dtype=jnp.int32)[None, :] >= (-sizes % g)[:, None]  # (E', g - 1)
    fillers = jnp.where(spare, e_local, jnp.arange(e_local, dtype=jnp.int32)[:, None]).reshape(-1)
    keys = jnp.concatenate([jnp.where(held, local, e_local), fillers])
    pairs = jnp.concatenate([jnp.arange(M, dtype=jnp.int32), jnp.full(fillers.shape, M, jnp.int32)])  # M: a filler
    # stable: an expert's pairs stay token-major and come before its fillers
    row_expert, pair = jax.lax.sort((keys, pairs), num_keys=1, is_stable=True)
    _, lies = jax.lax.sort((pair, jnp.arange(pair.shape[0], dtype=jnp.int32)), num_keys=1)  # the pairs first, in order
    row_expert, pair = row_expert[:plan.rows], pair[:plan.rows]  # every held pair lies inside
    return Layout(plan, jnp.where(pair < M, pair // top_k, 0), jnp.minimum(row_expert, e_local - 1),
                  jnp.where(held, lies[:M], plan.rows), sizes, all_sizes)


def combine(y, back, weights):
    """Each token's chosen rows of ``y`` (the layout's rows, (rows, H)) summed
    under the routing weights (N, k) -> (N, H) float32. ``back`` (N*k,) says
    where each (token, choice) lies, ``rows`` where this shard holds none of
    it. The rows are gathered choice-major, (k, N, H) in ``y``'s own dtype: k
    is a major axis, so the tiles are (N, H) whatever k is (token-major, a
    float32 (N, k, H) pads each token's k rows to whole sublane tiles: a
    copy of 16 rows for 10, ``PERF.md`` section 6, PR 51), and N is padded to
    whole bf16 tiles of 16 rows, so that (k, N, H) is the gather as it lies.
    The sum is one elementwise chain over the choices in their order, so a
    token's sum is the same to the bit wherever it lies in the step and
    whatever else the step holds. A row this shard does not hold is selected
    away, not weighted away: the clamped row may hold anything."""
    rows = y.shape[0]
    N, k = weights.shape
    pad = ((0, 0), (0, -N % 16))
    back = jnp.pad(back.reshape(N, k).T, pad, constant_values=rows)  # (k, N')
    by_choice = jnp.pad(weights.T, pad)
    picked = y[jnp.minimum(back, rows - 1)]  # (k, N', H)
    held = back < rows
    out = None
    for c in range(k):
        term = jnp.where(held[c][:, None], picked[c].astype(jnp.float32), 0.0) * by_choice[c][:, None]
        out = term if out is None else out + term
    return out[:N]


def _experts_of_shard(x, weights, expert, wg, wu, wd, layer, *, act, kernel, ep, tp, total=None, first=0):
    """Everything after the router, for the experts (and, under ``tp``,
    the columns) this shard holds: lay the rows out, the two grouped
    matmuls, the combine per token. x (N, H), weights (N, k) and expert
    (N*k,) are whole on every shard; the stacks are the shard's own
    (L, E/ep, K, N). Returns (this shard's part of the output (N, H)
    float32, rows each of ALL experts multiplied (E,)). With no ``ep`` axis
    the stacks may hold a chip's SHARE of ``total`` routed experts, from expert
    ``first`` on (ISSUE 54): a pair whose expert is not held lies nowhere and
    adds nothing, and the rows counted are the held experts' alone."""
    top_k = weights.shape[1]
    e_local = jax.tree.leaves(wg)[0].shape[1]
    with jax.named_scope("moe.dispatch"):
        e = e_local * jax.lax.axis_size(ep) if ep else (total or e_local)
        lay = dispatch(expert, top_k, e, e_local, jax.lax.axis_index(ep) * e_local if ep else first)
        rows = x[lay.token]
    with jax.named_scope("moe.experts"):
        h = grouped_matmul(rows, (wg, wu), layer, lay.sizes, lay.plan, lay.row_expert, act=act, kernel=kernel)
        # tp shards hold partial sums of the down matrix: kept float32 until summed.
        y = grouped_matmul(h, (wd,), layer, lay.sizes, lay.plan, lay.row_expert, kernel=kernel,
                           out_dtype=jnp.float32 if tp else None)
    with jax.named_scope("moe.combine"):
        out = combine(y, lay.back, weights)
        axes = tuple(a for a in (ep, tp) if a)
        if axes:
            out = jax.lax.psum(out, axes)
    if not ep and e != e_local:   # a share: no one here multiplied the other experts' rows
        at = jnp.arange(e, dtype=jnp.int32) - first
        return out, jnp.where((at >= 0) & (at < e_local), lay.all_sizes, 0)
    return out, lay.all_sizes


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
    first: int = 0,  # the stacks hold a share of the E routed experts, from this one on (one device; ISSUE 54)
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
                                      act=act, kernel=kernel, ep=None, tp=None, total=E, first=first)
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
