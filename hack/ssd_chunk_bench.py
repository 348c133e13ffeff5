"""Time `ops/ssd.ssd_chunk`'s Pallas kernel alone on the chip (ISSUE 65, step 0).

By hand, through chiprun, one process a reading (a chip belongs to one process
at a time):

    chiprun -- sh -c 'python3 hack/ssd_chunk_bench.py; \
                      python3 hack/ssd_chunk_bench.py --live-visits-only; \
                      python3 hack/ssd_chunk_bench.py --oracle-root scratch_checkout/parent'

One launch at the Granite cell's mixed shape: 544 packed tokens (the kernel
pads them to 640), 128 heads of 64 with a state of 128 columns, blocks of 128,
products on operands rounded to bfloat16, a store of (9, 32, 128, 64, 128)
float32, and `ops/ssd.block_plan`'s visits for one 512-token row beside 31
one-token rows in a seeded slot order (5 live visits of the plan's 36). The
launch is chained CALLS times in one program, the layer ordinal going round
the store's entries and the store carried in place; the host clock around
`block_until_ready` (median of REPEATS) and, from a capture of one such
program, the kernel's own events by name: their median is the number the
cell's `ssd_chunk_roofline_share` divides by. Beside it the bytes a launch has
to move and the operations it has to do, as `bench/mamba2_bytes.py` counts
them a visit for the live visits, and the v5e's least time for them.

`--live-visits-only` hands the launch the plan CUT to its live visits (a
static grid of 5 visits where the plan holds 36): what a launch costs with no
visit past the last in it, whatever the tree does with such a visit. The
difference from the whole plan's reading is what the visits past the last
cost. `--root` times another checkout's `ops/ssd.py` (its imports are this
checkout's); `--oracle-root` holds this checkout's launch to another
checkout's on the same operands, outputs and store, TO THE BIT. The check that
rides along is the largest difference of the outputs and of the chunk row's
last state from this checkout's `ssd_chunk_twin` on the same device.

Not part of the benchmark, imported by nothing a cell runs; a CPU run of it
(`--rehearse`, a tiny shape through the interpreted kernel) times the CPU and
is never a speed number.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

from kda_chunk_bench import _kernel_us, _rows   # the capture's reduction and the seeded rows, as KDA's bench has them

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CALLS, REPEATS = 18, 9

# packed tokens, heads, head width, state columns, block, slots, state layers, the chunk's tokens, one-token rows
CELL = dict(T=544, Hh=128, P=64, N=128, Q=128, S=32, L=9, chunk=512, ones=31)
TINY = dict(T=48, Hh=8, P=16, N=16, Q=16, S=6, L=2, chunk=40, ones=3)


def _load(root: str):
    path = os.path.join(os.path.abspath(root), "inference_gateway_tpu", "ops", "ssd.py")
    spec = importlib.util.spec_from_file_location(f"timed_ssd_{abs(hash(path))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def measure(ssd, oracle, shape: dict, repeats: int, seed: int, live_only: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.ops import ssd as here_ssd

    T, Hh, P, N, Q, S, L = (shape[n] for n in ("T", "Hh", "P", "N", "Q", "S", "L"))
    dev = jax.devices()[0]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, Hh, P)), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (T, Hh))), jnp.float32)
    B = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    A = -jnp.asarray(np.exp(rng.uniform(0.0, np.log(16.0), Hh)), jnp.float32)
    starts, lens, position = _rows(rng, S, shape["chunk"], shape["ones"])
    meta, blocks = here_ssd.block_plan(jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(position + lens), T, Q)
    planned = int(meta.shape[0])
    if live_only:
        meta = meta[:max(int(blocks), 1)]
    store = jnp.asarray(rng.normal(size=(L, S, Hh, P, N)), jnp.float32)
    mm = jnp.bfloat16
    row = int(lens.argmax())                                                    # the chunk's slot
    at = slice(int(starts[row]), int(starts[row] + lens[row]))
    launch = lambda mod, s, o: mod.ssd_chunk(s, o, x, dt, B, C, A, meta, Q=Q, mm=mm, kernel=True)

    @jax.jit
    def chained(store_):
        def body(i, carry):
            store__, seen = carry
            y, store__ = launch(ssd, store__, i % L)
            return store__, seen + y[at.start]
        return jax.lax.fori_loop(0, CALLS, body, (store_, jnp.zeros((Hh, P), jnp.float32)))

    jax.block_until_ready(chained(store))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(store))
        times.append((time.perf_counter() - t0) / CALLS)
    line = {"shape": shape, "visits_live": float(blocks), "visits_planned": planned, "visits_handed": int(meta.shape[0]),
            "head_blocks": Hh // ssd.HEAD_BLOCK if Hh % ssd.HEAD_BLOCK == 0 else 1, "chunk_starts_at": at.start,
            "host_us_a_launch": round(statistics.median(times) * 1e6, 1), "host_us_min": round(min(times) * 1e6, 1)}
    if dev.platform == "tpu":
        line.update(_kernel_us(lambda: jax.block_until_ready(chained(store)), "ssd_chunk_kernel"))
        sys.path.insert(0, os.path.join(HERE, "bench"))
        import mamba2_bytes

        chip = json.load(open(os.path.join(HERE, "bench", "chips.json")))[dev.device_kind]
        config = json.load(open(os.path.join(HERE, "bench", "configs", "granite-4.0-h-small-int8.json")))
        least = float(blocks) * max(mamba2_bytes.block_flops(config) / chip["bf16_flops_per_s"],
                                    mamba2_bytes.block_bytes(config) / chip["hbm_bytes_per_s"])
        line["least_us"] = round(least * 1e6, 1)
        if line.get("kernel_us"):
            line["share_of_least_pct"] = round(100 * line["least_us"] / line["kernel_us"], 2)
    # Against this checkout's twin, on this device: the chunk row's outputs and its slot's last state.
    got_y, got_s = jax.jit(lambda s: launch(ssd, s, 1))(store)
    want_y, want_s = jax.jit(lambda s: here_ssd.ssd_chunk_twin(s, 1, x, dt, B, C, A, meta, Q=Q, mm=mm))(store)
    line["max_abs_diff_vs_twin"] = {"y": float(jnp.max(jnp.abs(got_y[at] - want_y[at]))),
                                    "state": float(jnp.max(jnp.abs(got_s[1, row] - want_s[1, row]))),
                                    "y_scale": float(jnp.max(jnp.abs(want_y[at])))}
    if oracle is not None:   # another checkout's launch on the same operands: the chunk row's outputs and the WHOLE store
        their_y, their_s = jax.jit(lambda s: launch(oracle, s, 1))(store)
        line["equal_to_the_oracle_bit_for_bit"] = {"y": bool(jnp.all(got_y[at] == their_y[at])),
                                                   "store": bool(jnp.all(got_s == their_s))}
    return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE, help="checkout whose ops/ssd.py to time")
    ap.add_argument("--oracle-root", help="a checkout whose launch this one's has to equal to the bit")
    ap.add_argument("--live-visits-only", action="store_true", help="hand the launch the plan cut to its live visits")
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--seed", type=int, default=65)
    ap.add_argument("--rehearse", action="store_true", help="a tiny shape, for a walk through on the CPU")
    ap.add_argument("--tag")
    args = ap.parse_args()
    sys.path.insert(0, HERE)

    import jax

    dev = jax.devices()[0]
    out = {"root": os.path.relpath(os.path.abspath(args.root), HERE),
           "device": {"platform": dev.platform, "kind": dev.device_kind}, "calls_chained": CALLS,
           **measure(_load(args.root), _load(args.oracle_root) if args.oracle_root else None,
                     TINY if args.rehearse else CELL, 2 if args.rehearse else args.repeats, args.seed, args.live_visits_only)}
    dest = os.path.join(HERE, "chiprun_out", "ssd_chunk_bench")
    os.makedirs(dest, exist_ok=True)
    tag = args.tag or (("change" if os.path.abspath(args.root) == HERE else os.path.basename(os.path.abspath(args.root)))
                       + ("-live" if args.live_visits_only else ""))
    with open(os.path.join(dest, f"{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(tag, json.dumps(out), flush=True)
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    raise SystemExit(main())
