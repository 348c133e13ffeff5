"""Time the sampler's candidate selection alone on the chip (ISSUE 31, ISSUE 57).

By hand, through chiprun: `chiprun -- python3 hack/topk_bench.py`. Times
`jax.lax.top_k` against the grouped selection at the serving shapes and at
every group width, by the host clock over chained calls and by the device's
own operation line of a profiler trace, and says of each compiled program
whether it holds a sort or a TopK custom call. Not part of the benchmark; a
CPU run of it times the CPU and is never a speed number.
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from inference_gateway_tpu.ops import sampling  # noqa: E402
from inference_gateway_tpu.serving import speculative  # noqa: E402

# The cells' (rows..., V): openPangu's round selects over its verify queries as
# three axes and over its drafts as two; Mistral's decode step; two of the ten
# large vocabularies as the controls (ISSUE 57).
SHAPES = [(64, 2, 19200), (128, 19200), (64, 19200), (24, 32000), (48, 32000), (48, 151936), (48, 152064)]
WIDTHS = (16, 32, 64, 128, 256)
STRIDES = (256, 768, 1280)  # the strided form's lane-dense minor axis: V / S columns a group
K = 64


def folded(f):
    """``f`` over (rows, V): the leading axes folded into rows and restored."""
    def g(x, *a, **kw):
        lead = x.shape[:-1]
        out = f(x.reshape(-1, x.shape[-1]), *a, **kw)
        return jax.tree_util.tree_map(lambda o: o.reshape(*lead, *o.shape[1:]), out)
    return g


grouped = folded(sampling.grouped_top_k)  # the module's selection at ANY width, whatever its plan says


@folded
def strided(x, k, S, tiebreak):
    """Groups strided over a lane-dense reshape [rows, V / S, S]: group j is the
    columns j, j + S, ... Tried and not taken. As it stands a tie between groups
    is NOT broken by the lower column (``_exact`` reads false on the integer
    input); ``tiebreak`` makes it exact: among the groups whose maximum ties with
    the k-th largest, those whose FIRST such column is lowest (a second TopK over S)."""
    V = x.shape[-1]
    n = -(-V // S)
    if n * S != V:
        x = jnp.pad(x, [(0, 0), (0, n * S - V)], constant_values=-jnp.inf)
    xg = x.reshape(x.shape[0], n, S)
    m = jnp.max(xg, axis=1)
    if tiebreak:
        col = jnp.argmax(xg, axis=1) * S + jnp.arange(S)
        t = jax.lax.top_k(m, k)[0][:, -1:]
        m = jnp.where(m > t, jnp.inf, jnp.where(m == t, -col.astype(jnp.float32), -jnp.inf))
    _, gid = jax.lax.top_k(m, k)
    gid = jnp.sort(gid, axis=-1)
    cand = jnp.take_along_axis(xg, gid[:, None, :], axis=2)  # (rows, n, k): column order
    vals, pos = jax.lax.top_k(cand.reshape(x.shape[0], n * k), k)
    return vals, (pos // k) * S + jnp.take_along_axis(gid, pos % k, axis=-1)


def stage1(x, g):
    return jnp.max(x.reshape(x.shape[0], -1, g), axis=-1)


def stage12(x, k, g):
    _, gid = jax.lax.top_k(stage1(x, g), k)
    return jnp.sort(gid, axis=-1)


def stage123(x, k, g):
    gid = stage12(x, k, g)
    return jnp.take_along_axis(x.reshape(x.shape[0], -1, g), gid[..., None], axis=-2)


def named(name, f):
    """jit ``f`` under a program name the trace's module line will carry."""
    def g(a):
        return f(a)
    g.__name__ = g.__qualname__ = name
    return jax.jit(g)


def wall_ms(fn, *args, n=200):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def selections_of(fn, x):
    """What the compiled program selects with: its sorts (with their operand
    shapes) and its custom calls, read from the optimised HLO."""
    text = fn.lower(x).compile().as_text()
    sorts = re.findall(r"= \(?([a-z0-9]+\[[0-9,]*\])[^\n]* sort\(", text)
    calls = re.findall(r'custom_call_target="([^"]+)"', text)
    return {"sorts": sorts, "custom_calls": sorted(set(calls))}


def device_times(trace_dir):
    """({program: (count, total ms)}, {program: {op: total ms}}) of the first
    device plane: an operation belongs to the program whose event holds it."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        return {}, {}
    data = ProfileData.from_file(paths[-1])
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines if line.name in ("XLA Modules", "XLA Ops")}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        progs, ops = {}, {}
        for start, end, name in mods:
            c, t = progs.get(name, (0, 0.0))
            progs[name] = (c + 1, t + (end - start) / 1e6)
        for ev in lines.get("XLA Ops", []):
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i >= 0 and ev.start_ns < mods[i][1]:
                per = ops.setdefault(mods[i][2], {})
                per[ev.name] = per.get(ev.name, 0.0) + ev.duration_ns / 1e6
        return progs, ops
    return {}, {}


def main():
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}))
    rng = np.random.default_rng(31)
    results = []
    progs = {}
    for shape in SHAPES:
        V, tag = shape[-1], "x".join(map(str, shape))
        x = rng.standard_normal(shape).astype(np.float32) * 3
        x[..., 32:127] += 100.0  # the benchmark's bias: every winner in one group
        xi = rng.integers(-4, 5, shape).astype(np.float32)  # ties everywhere
        xd, xid = jnp.asarray(x), jnp.asarray(xi)
        lead = shape[:-1]
        temp = jnp.full(lead, 0.7, jnp.float32)
        top_p = jnp.full(lead, 1.0, jnp.float32)
        gum = jnp.asarray(rng.gumbel(size=(*lead, K)).astype(np.float32))
        fns = {"direct": lambda a: jax.lax.top_k(a, K),
               "module": lambda a: sampling.select_top_k(a, K)}
        if len(shape) > 2:
            fns["direct_folded"] = folded(lambda a: jax.lax.top_k(a, K))
        for g in WIDTHS:
            if V % g == 0 and V // g >= K:
                fns[f"grouped_g{g}"] = functools.partial(grouped, k=K, g=g)
        for S in STRIDES:
            if V % S == 0 and V // S <= 128:
                fns[f"strided_s{S}"] = functools.partial(strided, k=K, S=S, tiebreak=False)
                fns[f"strided_tiebreak_s{S}"] = functools.partial(strided, k=K, S=S, tiebreak=True)
        if V % 128 == 0 and V // 128 >= K:
            fns["stage1_g128"] = folded(functools.partial(stage1, g=128))
            fns["stage12_g128"] = folded(functools.partial(stage12, k=K, g=128))
            fns["stage123_g128"] = folded(functools.partial(stage123, k=K, g=128))
        fns = {name: named(f"{name}_{tag}", f) for name, f in fns.items()}
        ref_v, ref_i = fns["direct"](xd)
        ref_vi, ref_ii = fns["direct"](xid)
        row = {"shape": list(shape), "plan": sampling.top_k_plan(V, K)}
        for name, fn in fns.items():
            if not name.startswith("stage"):
                v, i = fn(xd)
                vi, ii = fn(xid)
                row[name + "_exact"] = bool(
                    (np.asarray(v) == np.asarray(ref_v)).all() and (np.asarray(i) == np.asarray(ref_i)).all()
                    and (np.asarray(vi) == np.asarray(ref_vi)).all() and (np.asarray(ii) == np.asarray(ref_ii)).all())
                row[name + "_hlo"] = selections_of(fn, xd)
            row[name + "_ms"] = round(wall_ms(fn, xd), 4)
            progs[f"{tag}:{name}"] = (fn, (xd,))
        # the whole sampler body as the step programs run it: the decode loop's
        # over two axes, the drafting round's verify strip over three
        if len(shape) == 2:
            body = lambda l, t, p, gmb: sampling.sample_tokens_pregumbel(l, t, p, gmb, K)
        else:
            body = lambda l, t, p, gmb: speculative.strip_dist(l, t, p, K)
        samp = jax.jit(lambda *a: body(*a))
        row["sampler_module_ms"] = round(wall_ms(samp, xd, temp, top_p, gum), 4)
        orig = sampling.select_top_k
        sampling.select_top_k = jax.lax.top_k
        try:
            samp_d = jax.jit(lambda *a: body(*a))
            row["sampler_direct_ms"] = round(wall_ms(samp_d, xd, temp, top_p, gum), 4)
            pairs = [(np.asarray(a), np.asarray(b)) for a, b in zip(
                jax.tree_util.tree_leaves(samp_d(xd, temp, top_p, gum)),
                jax.tree_util.tree_leaves(samp(xd, temp, top_p, gum)))]
        finally:
            sampling.select_top_k = orig
        # tokens and ids are integers; a strip's probabilities come out of another fusion of the same arithmetic
        row["sampler_same_tokens"] = all(bool((a == b).all()) for a, b in pairs if a.dtype.kind == "i")
        row["sampler_max_abs_diff"] = max([float(np.abs(a - b).max()) for a, b in pairs if a.dtype.kind == "f"], default=0.0)
        results.append(row)
        print(json.dumps(row), flush=True)

    trace_dir = os.path.join("chiprun_out", "topk_trace")
    try:
        jax.profiler.start_trace(trace_dir)
        for name, (fn, args) in progs.items():
            for _ in range(10):
                out = fn(*args)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        mods, ops = device_times(trace_dir)
        dev_rows = [{"name": name[:160], "count": c, "ms_each": round(t / c, 4),
                     "ops_ms_each": {op[:60]: round(ms / c, 4) for op, ms in
                                     sorted(ops.get(name, {}).items(), key=lambda kv: -kv[1])[:4]}}
                    for name, (c, t) in sorted(mods.items())]
    except Exception as e:  # the trace is a second opinion, not the result
        dev_rows = [{"error": repr(e)}]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/topk_bench.json", "w") as f:
        json.dump({"rows": results, "device_ops": dev_rows}, f, indent=1)
    for r in dev_rows:
        print(json.dumps(r))
    # the traces are large; the summary is what comes back
    shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
