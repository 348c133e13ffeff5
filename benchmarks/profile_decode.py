"""Decompose the serving decode step's cost on real hardware.

The round-3 bench reads 6.7k tok/s/chip at 64 slots = 9.5 ms per token
step, vs a ~2.7 ms weight-streaming roofline for TinyLlama bf16. This
script attributes the gap:

1. dispatch overhead vs per-step compute — time `engine.decode_chunk`
   at n_steps in {1, 4, 8, 16, 32, 64} and fit t = overhead + n * step;
2. paged-attention share — same sweep with attention="dense";
3. weight-streaming share — same sweep with int8 weight-only quant
   (halves the weight bytes; if decode is weight-bound, step time drops
   ~2x);
4. per-kernel sanity: the paged kernel timed over 40 DISTINCT query
   buffers (the kernel-lab's rotated-4 measurement could still be
   short-circuited by anything that caches per exact input set).

Run from repo root: python benchmarks/profile_decode.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def time_chunks(engine, batch, prompt_len, n_steps_list):
    import jax

    rng = np.random.default_rng(0)
    V = engine.model_cfg.vocab_size
    S = engine.config.max_slots
    slots = list(range(batch))
    for group_start in range(0, batch, engine.config.max_prefill_batch):
        group = slots[group_start:group_start + engine.config.max_prefill_batch]
        prompts = [[int(x) for x in rng.integers(1, V - 1, prompt_len)] for _ in group]
        engine.prefill(prompts, group, [0.0] * len(group), [1.0] * len(group))

    tokens = np.zeros((S,), np.int32)
    positions = np.zeros((S,), np.int32)
    active = np.zeros((S,), bool)
    temps = np.zeros((S,), np.float32)
    top_ps = np.ones((S,), np.float32)
    active[:batch] = True
    pos = prompt_len

    out = {}
    for n in n_steps_list:
        positions[:batch] = pos
        # warm/compile this n_steps shape
        engine.decode_chunk(tokens, positions, active, temps, top_ps, n_steps=n)
        pos += n
        iters = max(2, min(10, 256 // n))
        t0 = time.perf_counter()
        for _ in range(iters):
            positions[:batch] = pos
            engine.decode_chunk(tokens, positions, active, temps, top_ps, n_steps=n)
            pos += n
        dt = (time.perf_counter() - t0) / iters
        out[n] = dt * 1e3  # ms per chunk
        print(f"  n_steps={n}: {dt * 1e3:8.2f} ms/chunk = {dt / n * 1e3:6.2f} ms/step "
              f"-> {batch * n / dt:8.0f} tok/s", file=sys.stderr, flush=True)
    for s in slots:
        engine.release_slot(s)
    # Least-squares fit t_ms = overhead + n * per_step over the sweep.
    ns = np.array(sorted(out))
    ts = np.array([out[n] for n in ns])
    A = np.vstack([np.ones_like(ns), ns]).T.astype(float)
    (overhead, per_step), *_ = np.linalg.lstsq(A, ts, rcond=None)
    return {"ms_per_chunk": {int(k): round(v, 2) for k, v in out.items()},
            "fit_overhead_ms": round(float(overhead), 2),
            "fit_per_step_ms": round(float(per_step), 3)}


def build_engine(attention="paged", quantize=None):
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.profiles import PROFILES

    p = PROFILES["v5e-1-tinyllama"]
    kw = p.engine_kwargs()
    kw["attention"] = attention
    kw["quantize"] = quantize
    return Engine(EngineConfig(**kw)), p


def kernel_distinct_inputs(iters=40):
    import jax
    import jax.numpy as jnp

    from inference_gateway_tpu.ops.paged_attention import paged_attention_tpu

    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, ps = 64, 32, 4, 64, 64
    P, mp = 512, 16
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), jnp.bfloat16)
    pt = jnp.asarray(rng.integers(0, P, (B, mp)), jnp.int32)
    lengths = jnp.full((B,), 512, jnp.int32)
    qs = [jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.bfloat16) for _ in range(iters)]
    r = paged_attention_tpu(qs[0], k, v, pt, lengths, Hkv)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    rs = [paged_attention_tpu(q, k, v, pt, lengths, Hkv) for q in qs]
    jax.block_until_ready(rs)
    return round((time.perf_counter() - t0) / iters * 1e6, 1)


def main():
    results = {}
    results["paged_kernel_distinct_inputs_us"] = kernel_distinct_inputs()
    print(f"paged kernel, 40 distinct inputs: "
          f"{results['paged_kernel_distinct_inputs_us']} us/call", file=sys.stderr)

    sweep = [1, 4, 8, 16, 32, 64]
    for name, attention, quantize in [
        ("paged_bf16", "paged", None),
        ("dense_bf16", "dense", None),
        ("paged_int8", "paged", "int8"),
    ]:
        print(f"[{name}] building engine", file=sys.stderr, flush=True)
        engine, p = build_engine(attention, quantize)
        batch = p.max_slots
        print(f"[{name}] sweep (batch={batch})", file=sys.stderr, flush=True)
        results[name] = time_chunks(engine, batch, 128, sweep)
        del engine

    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
