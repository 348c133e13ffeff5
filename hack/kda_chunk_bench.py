"""Time `ops/kda.kda_chunk`'s Pallas kernel alone on the chip (ISSUE 55).

By hand, through chiprun, parent and change in one call (a chip belongs to
one process at a time, so each side is a process of its own):

    chiprun -- sh -c 'python3 hack/kda_chunk_bench.py --root scratch_checkout/parent; \
                      python3 hack/kda_chunk_bench.py'

One launch at the Kimi-Linear cell's mixed shape: 544 packed tokens (the
kernel pads them to 576), 32 heads of 128, blocks of 64, products on operands
rounded to bfloat16, a store of (6, 32, 32, 128, 128) float32, and
`ops/ssd.block_plan`'s visits for one 512-token row beside 15 one-token rows
in a seeded slot order (9 live visits of the 40 the grid holds). The launch
is chained CALLS times in one program, the layer ordinal going round the six
entries and the store carried in place; the host clock around
`block_until_ready` (median of REPEATS) and, from a capture of one such
program, the kernel's own events by name: their median is the number the
cell's `kda_chunk_roofline_share` divides by. Beside it the bytes a launch
has to move and the operations it has to do, as `bench/kda_bytes.py` counts
them a visit (`block_flops`, `block_bytes`) for the live visits, and the v5e's
least time for them: the cell's `kda_chunk_roofline_share`, one launch.

`--file` times another copy of `ops/kda.py` (an ablation: a copy with a part
of the kernel cut out gives wrong answers and a right time); its imports are
this checkout's. `--live-visits-only` (ISSUE 65, step 0) hands the launch the
plan CUT to its live visits (a static grid of 9 visits where the plan holds
40): what a launch costs with no visit past the last in it; `--oracle-root`
holds the timed launch to another checkout's on the same operands, outputs and
store, TO THE BIT. The check that rides along is the largest difference of the
outputs and of the chunk row's last state from this checkout's
`kda_chunk_twin` on the same device, every pair formed alone.

Not part of the benchmark, imported by nothing a cell runs; a CPU run of it
(`--rehearse`, a tiny shape through the interpreted kernel) times the CPU and
is never a speed number.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CALLS, REPEATS = 24, 9

# packed tokens, heads, head width, block, slots, state layers, the chunk's tokens, one-token rows
CELL = dict(T=544, Hh=32, K=128, Q=64, S=32, L=6, chunk=512, ones=15)
TINY = dict(T=48, Hh=4, K=32, Q=16, S=6, L=2, chunk=40, ones=3)


def _load(path: str):
    spec = importlib.util.spec_from_file_location(f"timed_kda_{abs(hash(path))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _kernel_us(run, kernel: str = "kda_chunk_kernel") -> dict:
    """One ``run()`` under the profiler: the events of ``kernel`` by name, us."""
    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            run()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        events = [ev.duration_ns / 1e3
                  for plane in ProfileData.from_file(path).planes if plane.name.startswith("/device:TPU:")
                  for line in plane.lines if line.name == "XLA Ops"
                  for ev in line.events if kernel in ev.name]
    if not events:
        return {"kernel_events": 0}
    return {"kernel_events": len(events), "kernel_us": round(statistics.median(events), 1),
            "kernel_us_min": round(min(events), 1), "kernel_us_max": round(max(events), 1)}


def _rows(rng, S: int, chunk: int, ones: int):
    """One row of ``chunk`` tokens beside ``ones`` one-token rows, packed in slot
    order; the chunk's slot is not the first live one, so it starts inside a block."""
    import numpy as np

    slots = np.sort(rng.choice(S, ones + 1, replace=False))
    lens = np.zeros(S, np.int32)
    lens[slots] = 1
    lens[slots[1 + rng.integers(ones)]] = chunk
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    position = np.where(lens > 0, rng.integers(64, 4096, S), 0).astype(np.int32)   # every row carries a state
    return starts, lens, position


def measure(kda, shape: dict, repeats: int, seed: int, live_only: bool = False, oracle=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.ops import kda as here_kda
    from inference_gateway_tpu.ops import ssd

    T, Hh, K, Q, S, L = (shape[n] for n in ("T", "Hh", "K", "Q", "S", "L"))
    dev = jax.devices()[0]
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = jnp.asarray(unit(rng.normal(size=(T, Hh, K))) * K ** -0.5, jnp.float32)
    k = jnp.asarray(unit(rng.normal(size=(T, Hh, K))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, Hh, K)), jnp.float32)
    g = jnp.asarray(-np.exp(rng.normal(size=(T, Hh, K)) * 1.5 - 4.0), jnp.float32)    # log decays over two decades
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(T, Hh)))), jnp.float32)
    starts, lens, position = _rows(rng, S, shape["chunk"], shape["ones"])
    meta, blocks = ssd.block_plan(jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(position + lens), T, Q)
    planned = int(meta.shape[0])
    if live_only:
        meta = meta[:max(int(blocks), 1)]
    store = jnp.asarray(rng.normal(size=(L, S, Hh, K, K)), jnp.float32)
    mm = jnp.bfloat16
    row = int(lens.argmax())                                                    # the chunk's slot
    at = slice(int(starts[row]), int(starts[row] + lens[row]))

    @jax.jit
    def chained(store_):
        def body(i, carry):
            store__, seen = carry
            o, store__ = kda.kda_chunk(store__, i % L, q, k, v, g, beta, meta, Q=Q, mm=mm, kernel=True)
            return store__, seen + o[at.start]
        return jax.lax.fori_loop(0, CALLS, body, (store_, jnp.zeros((Hh, K), jnp.float32)))

    jax.block_until_ready(chained(store))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(store))
        times.append((time.perf_counter() - t0) / CALLS)
    line = {"shape": shape, "visits_live": float(blocks), "visits_planned": planned, "visits_handed": int(meta.shape[0]),
            "chunk_starts_at": at.start,
            "host_us_a_launch": round(statistics.median(times) * 1e6, 1), "host_us_min": round(min(times) * 1e6, 1)}
    if dev.platform == "tpu":
        line.update(_kernel_us(lambda: jax.block_until_ready(chained(store))))
        sys.path.insert(0, os.path.join(HERE, "bench"))
        import kda_bytes

        chip = json.load(open(os.path.join(HERE, "bench", "chips.json")))[dev.device_kind]
        config = json.load(open(os.path.join(HERE, "bench", "configs", "kimi-linear-48b-a3b-int8.json")))
        least = float(blocks) * max(kda_bytes.block_flops(config) / chip["bf16_flops_per_s"],
                                    kda_bytes.block_bytes(config) / chip["hbm_bytes_per_s"])
        line["least_us"] = round(least * 1e6, 1)
        if line.get("kernel_us"):
            line["share_of_least_pct"] = round(100 * line["least_us"] / line["kernel_us"], 2)
    # Against this checkout's twin, on this device: the chunk row's outputs and its slot's last state.
    got_o, got_s = jax.jit(lambda s: kda.kda_chunk(s, 1, q, k, v, g, beta, meta, Q=Q, mm=mm, kernel=True))(store)
    want_o, want_s = jax.jit(lambda s: here_kda.kda_chunk_twin(s, 1, q, k, v, g, beta, meta, Q=Q, mm=mm))(store)
    line["max_abs_diff_vs_twin"] = {"o": float(jnp.max(jnp.abs(got_o[at] - want_o[at]))),
                                    "state": float(jnp.max(jnp.abs(got_s[1, row] - want_s[1, row]))),
                                    "o_scale": float(jnp.max(jnp.abs(want_o[at])))}
    if oracle is not None:   # another checkout's launch on the same operands: the chunk row's outputs and the WHOLE store
        their_o, their_s = jax.jit(lambda s: oracle.kda_chunk(s, 1, q, k, v, g, beta, meta, Q=Q, mm=mm, kernel=True))(store)
        line["equal_to_the_oracle_bit_for_bit"] = {"o": bool(jnp.all(got_o[at] == their_o[at])),
                                                   "store": bool(jnp.all(got_s == their_s))}
    return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE, help="checkout whose ops/kda.py to time")
    ap.add_argument("--file", help="a copy of ops/kda.py to time in its place (an ablation)")
    ap.add_argument("--oracle-root", help="a checkout whose launch the timed one has to equal to the bit")
    ap.add_argument("--live-visits-only", action="store_true", help="hand the launch the plan cut to its live visits")
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--rehearse", action="store_true", help="a tiny shape, for a walk through on the CPU")
    ap.add_argument("--tag")
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    path = os.path.abspath(args.file or os.path.join(args.root, "inference_gateway_tpu", "ops", "kda.py"))

    import jax

    dev = jax.devices()[0]
    out = {"file": os.path.relpath(path, HERE), "device": {"platform": dev.platform, "kind": dev.device_kind},
           "calls_chained": CALLS,
           **measure(_load(path), TINY if args.rehearse else CELL, args.repeats, args.seed, args.live_visits_only,
                     _load(os.path.join(args.oracle_root, "inference_gateway_tpu", "ops", "kda.py")) if args.oracle_root else None)}
    dest = os.path.join(HERE, "chiprun_out", "kda_chunk_bench")
    os.makedirs(dest, exist_ok=True)
    tag = args.tag or (("change" if path.startswith(os.path.join(HERE, "inference_gateway_tpu")) else
                        os.path.basename(os.path.dirname(path) if args.file else os.path.abspath(args.root)))
                       + ("-live" if args.live_visits_only else ""))
    with open(os.path.join(dest, f"{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(tag, json.dumps(out), flush=True)
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    raise SystemExit(main())
