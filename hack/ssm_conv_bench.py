"""Time `ops/ssm.conv_ragged` alone on the chip (ISSUE 53).

By hand, through chiprun, parent and change in one call (a chip belongs to
one process at a time, so each side is a process of its own):

    chiprun -- sh -c 'python3 hack/ssm_conv_bench.py --root scratch_checkout/parent; \
                      python3 hack/ssm_conv_bench.py --oracle-root scratch_checkout/parent'

One launch at each state cell's mixed shape (packed tokens x channels x slots,
taps, bias and activation as the model calls it): a 512-token chunk beside a
one-token row for every other slot, the slots packed in a seeded order, bf16
inputs as the input projection leaves them. The launch is chained CALLS times
in one program (an element of the result is written back into the inputs, the
new tails are the next call's), the host clock around `block_until_ready`
(median of REPEATS) and, from a capture of one such program, the device time:
the program's own event over CALLS, and the largest operations. Beside each:
the bytes the operator has to move (the inputs once in bf16, the result once in
float32, the tails read and written) and the v5e's least time for them
(`bench/chips.json`). ALONE the operator's arrays stay in the chip's fast
memory between calls, which a model's step does not grant: the cell's own
reading is `hack/scope_ops.py <capture> ssm.conv`.

Two checks ride along, on the device that ran it: the largest absolute
difference from `--oracle-root`'s operator (another checkout's, over the live
rows), and the invariant: a sequence run whole and run cut at CUTS, at another
packed offset with the tails carried, must agree to the bit
(`chunk_independent`).

Not part of the benchmark, imported by nothing a cell runs; a CPU run of it
(`--rehearse`) times the CPU and is never a speed number.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import statistics
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CALLS, REPEATS, CUTS = 16, 15, (1, 2, 5, 133, 134)

# name: (packed tokens, channels, slots, taps, bias, activation)
CASES = {
    "granite mixed": (544, 8448, 32, 4, True, "silu"),
    "phi4flash mixed": (576, 5120, 64, 4, True, "silu"),
    "lfm2 mixed": (576, 2048, 64, 3, False, None),
}
TINY = {"tiny mixed": (48, 256, 8, 4, True, "silu"), "tiny plain": (48, 256, 8, 3, False, None)}


def _load(root: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(root, "inference_gateway_tpu", "ops", "ssm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _device_us(run, calls: int, most: int = 8) -> dict:
    """One ``run()`` under the profiler: the program's device time a call, and its largest operations'."""
    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            run()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        program, by_op = 0.0, {}
        for plane in ProfileData.from_file(path).planes:
            if not re.match(r"^/device:TPU:\d+$", plane.name):
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    program += sum(ev.duration_ns for ev in line.events) / 1e3 / calls
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        if not ev.name.startswith("%while"):
                            by_op[ev.name] = by_op.get(ev.name, 0.0) + ev.duration_ns / 1e3 / calls
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:most]
    return {"device_us_a_call": round(program, 1), "device_us_by_op": {k[:72]: round(v, 1) for k, v in top}}


def _layout(rng, T: int, S: int):
    """A 512-token chunk (or what T leaves) beside one-token rows, the slots in a seeded order."""
    import numpy as np

    lens = np.ones(S, np.int32)
    lens[rng.integers(S)] = min(512, T - (S - 1))
    starts, at = np.zeros(S, np.int32), 0
    for s in rng.permutation(S):
        starts[s], at = at, at + lens[s]
    return starts, lens


def measure(ssm, cases, names, repeats, profile, oracle=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    chip = json.load(open(os.path.join(HERE, "bench", "chips.json"))).get(dev.device_kind)
    lines = {}
    for name, (T, C, S, K, bias, act_name) in cases.items():
        if names and name not in names:
            continue
        rng = np.random.default_rng(len(name))
        act = jax.nn.silu if act_name else None
        starts, lens = _layout(rng, T, S)
        fresh = rng.random(S) < 0.1
        u = jnp.asarray(rng.normal(size=(T, C)), jnp.bfloat16)
        tails = jnp.asarray(rng.normal(size=(S, K - 1, C)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, C)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(C,)), jnp.float32) if bias else None

        def conv(module):
            def call(u_, tails_, starts_, lens_, fresh_):
                seg = module.token_segments(starts_, lens_, u_.shape[0])
                return module.conv_ragged(u_, tails_, w, b, seg, fresh_, starts_, lens_, act=act)
            return call

        rows = (jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(fresh))

        @jax.jit
        def chained(u_, tails_, *rows_):
            def body(_, carry):
                u__, tails__ = carry
                c, new = conv(ssm)(u__, tails__, *rows_)
                return u__.at[0, 0].set(c[0, 0].astype(u__.dtype)), new
            return jax.lax.fori_loop(0, CALLS, body, (u_, tails_))

        jax.block_until_ready(chained(u, tails, *rows))
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(chained(u, tails, *rows))
            times.append((time.perf_counter() - t0) / CALLS)
        nbytes = T * C * (2 + 4) + 2 * S * (K - 1) * C * 4
        line = {"shape": [T, C, S, K], "bias": bias, "act": act_name, "chunk": int(lens.max()),
                "us_a_call": round(statistics.median(times) * 1e6, 1), "us_min": round(min(times) * 1e6, 1),
                "mbytes": round(nbytes / 1e6, 1)}
        if profile:
            line.update(_device_us(lambda: jax.block_until_ready(chained(u, tails, *rows)), CALLS))
        if chip is not None:
            line["least_us"] = round(nbytes / chip["hbm_bytes_per_s"] * 1e6, 1)
            line["share_of_bytes_pct"] = round(100 * line["least_us"] / line.get("device_us_a_call", line["us_a_call"]), 1)
        if oracle is not None:
            got, want, live = jax.jit(conv(ssm))(u, tails, *rows), jax.jit(conv(oracle))(u, tails, *rows), int(lens.sum())
            line["max_abs_diff_vs_oracle"] = float(jnp.max(jnp.abs(got[0][:live] - want[0][:live])))
            line["tails_equal_oracle"] = bool(jnp.array_equal(got[1], want[1]))
        line["chunk_independent"] = _chunk_independent(conv(ssm), rng, T, C, S, K)
        lines[name] = line
        print(name, json.dumps(line), flush=True)
    return lines


def _chunk_independent(call, rng, T: int, C: int, S: int, K: int) -> dict:
    """A sequence of 140 tokens whole (packed from row 0) against the same cut at
    each of CUTS (front at row 7, back at row 3 of a second step, tails
    carried), by the bits of results and final tails, on this device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    L = min(140, T - 8)
    seq = jnp.asarray(rng.normal(size=(L, C)), jnp.bfloat16)
    filler = jnp.asarray(rng.normal(size=(T, C)), jnp.bfloat16)
    tails = jnp.asarray(rng.normal(size=(S, K - 1, C)), jnp.float32)
    run = jax.jit(call)

    def step(piece, at, tails_, is_fresh):
        lens, starts, fresh = np.zeros(S, np.int32), np.zeros(S, np.int32), np.zeros(S, bool)
        lens[1], lens[0], starts[0], fresh[0] = at, piece.shape[0], at, is_fresh
        u = jax.lax.dynamic_update_slice(filler, piece, (at, 0))
        c, new = run(u, tails_, jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(fresh))
        return np.asarray(c[at:at + piece.shape[0]]), new

    bits = lambda a: np.ascontiguousarray(np.asarray(a), np.float32).view(np.uint32)
    whole, whole_tails = step(seq, 0, tails, True)
    out = {}
    for cut in (c for c in CUTS if c < L):
        front, carried = step(seq[:cut], 7, tails, True)
        back, cut_tails = step(seq[cut:], 3, carried, False)
        out[str(cut)] = bool(np.array_equal(bits(np.concatenate([front, back])), bits(whole))
                             and np.array_equal(bits(cut_tails[0]), bits(whole_tails[0])))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE, help="checkout whose operator to time")
    ap.add_argument("--cases", nargs="*")
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--rehearse", action="store_true", help="two tiny cases, for a walk through on the CPU")
    ap.add_argument("--oracle-root", help="a checkout whose operator the results are also compared with")
    ap.add_argument("--tag")
    args = ap.parse_args()
    root = os.path.abspath(args.root)

    import jax

    ssm = _load(root, "timed_ssm")
    oracle = _load(os.path.abspath(args.oracle_root), "oracle_ssm") if args.oracle_root else None
    dev = jax.devices()[0]
    profile = dev.platform == "tpu"   # the device's own time, from a capture
    out = {"root": os.path.relpath(root, HERE), "device": {"platform": dev.platform, "kind": dev.device_kind},
           "calls_chained": CALLS,
           "cases": measure(ssm, TINY if args.rehearse else CASES, args.cases, args.repeats, profile, oracle)}
    dest = os.path.join(HERE, "chiprun_out", "ssm_conv_bench")
    os.makedirs(dest, exist_ok=True)
    tag = args.tag or ("change" if root == HERE else os.path.basename(root))
    with open(os.path.join(dest, f"{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    raise SystemExit(main())
