"""Time the sampler's candidate selection alone on the chip (ISSUE 31).

By hand, through chiprun: `chiprun -- python3 hack/topk_bench.py`. Times
`jax.lax.top_k` against the grouped selection at the serving shapes, by
the host clock over chained calls and by the device's own operation line
of a profiler trace. Not part of the benchmark; a CPU run of it times the
CPU and is never a speed number.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from inference_gateway_tpu.ops import sampling  # noqa: E402

SHAPES = [(24, 32000), (48, 151936), (48, 152064)]
K = 64


def grouped(x, k, g):
    """ops/sampling.select_top_k traced with another group width, and
    grouped whatever its crossover says (both are read while tracing)."""
    lanes, ratio = sampling.GROUP_LANES, sampling.GROUPED_MIN_GROUPS_PER_K
    sampling.GROUP_LANES, sampling.GROUPED_MIN_GROUPS_PER_K = g, 0
    try:
        return sampling.select_top_k(x, k)
    finally:
        sampling.GROUP_LANES, sampling.GROUPED_MIN_GROUPS_PER_K = lanes, ratio


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


def device_ops(trace_dir):
    """{program or op name: (count, total ms)} of the first device plane."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        return {}
    out = {}
    data = ProfileData.from_file(paths[-1])
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for ev in line.events:
                key = f"{line.name}:{ev.name}"
                c, t = out.get(key, (0, 0.0))
                out[key] = (c + 1, t + ev.duration_ns / 1e6)
    return out


def main():
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}))
    rng = np.random.default_rng(31)
    results = []
    progs = {}
    for B, V in SHAPES:
        x = rng.standard_normal((B, V)).astype(np.float32) * 3
        x[:, 32:127] += 100.0  # the benchmark's bias: every winner in one group
        xi = rng.integers(-4, 5, (B, V)).astype(np.float32)  # ties everywhere
        xd, xid = jnp.asarray(x), jnp.asarray(xi)
        temp = jnp.full((B,), 0.7, jnp.float32)
        top_p = jnp.full((B,), 1.0, jnp.float32)
        gum = jnp.asarray(rng.gumbel(size=(B, K)).astype(np.float32))
        fns = {"direct": lambda a: jax.lax.top_k(a, K),
               "module": lambda a: sampling.select_top_k(a, K)}
        for g in (64, 128, 256):
            if V % g == 0:
                fns[f"grouped_g{g}"] = functools.partial(grouped, k=K, g=g)
        if V % 128 == 0:
            fns["stage1_g128"] = functools.partial(stage1, g=128)
            fns["stage12_g128"] = functools.partial(stage12, k=K, g=128)
            fns["stage123_g128"] = functools.partial(stage123, k=K, g=128)
        fns = {name: named(f"{name}_{B}x{V}", f) for name, f in fns.items()}
        ref_v, ref_i = fns["direct"](xd)
        ref_vi, ref_ii = fns["direct"](xid)
        row = {"shape": [B, V]}
        for name, fn in fns.items():
            if name.startswith(("module", "grouped")):
                v, i = fn(xd)
                vi, ii = fn(xid)
                row[name + "_exact"] = bool(
                    (np.asarray(v) == np.asarray(ref_v)).all() and (np.asarray(i) == np.asarray(ref_i)).all()
                    and (np.asarray(vi) == np.asarray(ref_vi)).all() and (np.asarray(ii) == np.asarray(ref_ii)).all())
            row[name + "_ms"] = round(wall_ms(fn, xd), 4)
            progs[f"{B}x{V}:{name}"] = (fn, (xd,))
        # the whole sampler body as the decode loop runs it, both selections
        samp = jax.jit(lambda l, t, p, gmb: sampling.sample_tokens_pregumbel(l, t, p, gmb, K))
        row["sampler_module_ms"] = round(wall_ms(samp, xd, temp, top_p, gum), 4)
        orig = sampling.select_top_k
        sampling.select_top_k = jax.lax.top_k
        try:
            samp_d = jax.jit(lambda l, t, p, gmb: sampling.sample_tokens_pregumbel(l, t, p, gmb, K))
            row["sampler_direct_ms"] = round(wall_ms(samp_d, xd, temp, top_p, gum), 4)
            same = bool((np.asarray(samp_d(xd, temp, top_p, gum)) == np.asarray(samp(xd, temp, top_p, gum))).all())
        finally:
            sampling.select_top_k = orig
        row["sampler_same_tokens"] = same
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
        ops = device_ops(trace_dir)
        mods = sorted((kv for kv in ops.items() if kv[0].startswith("XLA Modules")), key=lambda kv: kv[0])
        top = mods + sorted((kv for kv in ops.items() if not kv[0].startswith("XLA Modules")),
                            key=lambda kv: -kv[1][1])[:50]
        dev_rows = [{"name": k[:160], "count": c, "ms_each": round(t / c, 4)} for k, (c, t) in top]
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
