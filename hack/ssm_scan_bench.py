"""Time Mamba-1's selective scan alone on the chip (ISSUE 64, step 0): the chunk
kernel `ops/ssm.ssm_scan_chunk_kernel`, the one-token form (`scan_decode` plain and
the kernel `ssm_scan_decode_kernel`, on a mixed step's one-token rows and on a decode
step's every slot), and the token loop `scan_ragged` they replace in a mixed step, at Jamba2-3B's mixed shape
(544 packed tokens: one 512-token row beside 31 one-token rows, Di 5,120, N 16, a
store of (LAYERS, 32, 16, 5,120) float32) and at the hybrid's (576 tokens, 64 slots).

    chiprun -- python3 hack/ssm_scan_bench.py [--attention] [--shape jamba|phi4flash]

Each form is chained CALLS times in one program, the layer ordinal going round
the store's entries and the store carried in place; the host clock around
`block_until_ready` (median of REPEATS) and, on a TPU, the kernel's own events in
a capture. Beside them what a launch HAS to do: the bytes' time (Delta, c, B and C
in, y out, float32 as the kernel reads them, B and C as the lane-replicated
columns it is handed; a chunk row's state once in and once out) at the chip's
bandwidth, and a count of vector operations (a token a 128 channels: 2
broadcasts, 2 + 2 + 2 + 2 multiplies, 2 additions, the sum over N in 1 + 3, a
select: 17, and 2 exponentials) at four vector slots a cycle of 940 MHz: the
body is vector work, so that count is the ceiling, not the bytes.

`--live-visits-only` (ISSUE 65, step 0) hands the chunk kernel the plan CUT to
its live visits (a static grid of 9 visits where the plan holds 40): what a
launch costs with no visit past the last in it; `--forms chunk_kernel` times
that form alone; `--oracle-root` holds the chunk kernel's launch to another
checkout's `ops/ssm.py` on the same operands, outputs and store, TO THE BIT.

`--attention` (step 0 (a)): both paged kernels at (Hq 20, Hkv 1, D 128, page
128), 100 pages a row, against the gather path on the same operands.

Not part of the benchmark, imported by nothing a cell runs; `--rehearse` walks a
tiny shape here through the interpreted kernel and is never a speed number.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CALLS, REPEATS = 26, 7
VECTOR_OPS, EXPS, SLOTS_A_CYCLE, CLOCK_HZ = 17, 2, 4, 940e6

SHAPES = {
    "jamba": dict(T=544, Di=5120, N=16, S=32, L=4, chunk=512, ones=31),
    "phi4flash": dict(T=576, Di=5120, N=16, S=64, L=4, chunk=512, ones=40),
    "tiny": dict(T=80, Di=256, N=8, S=6, L=2, chunk=66, ones=3),
}


def _events(run, needle: str) -> list:
    """One ``run()`` under the profiler: the device events whose name (the whole instruction, its result first) begins as ``needle``, us."""
    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            run()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        return [ev.duration_ns / 1e3
                for plane in ProfileData.from_file(path).planes if plane.name.startswith("/device:TPU:")
                for line in plane.lines if line.name == "XLA Ops"
                for ev in line.events if re.match(needle, ev.name)]


def _rows(rng, S: int, chunk: int, ones: int):
    import numpy as np

    slots = np.sort(rng.choice(S, ones + 1, replace=False))
    lens = np.zeros(S, np.int32)
    lens[slots] = 1
    lens[slots[rng.integers(ones + 1)]] = chunk
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    position = np.where(lens > 0, rng.integers(64, 4096, S), 0).astype(np.int32)   # every row carries a state
    return starts, lens, position


def _timed(fn, arg, repeats: int) -> float:
    import jax

    jax.block_until_ready(fn(arg))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(arg))
        times.append((time.perf_counter() - t0) / CALLS)
    return statistics.median(times) * 1e6


def _load(root: str):
    """Another checkout's ``ops/ssm.py`` (its imports are this checkout's)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("oracle_ssm", os.path.join(root, "inference_gateway_tpu", "ops", "ssm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def measure(shape: dict, repeats: int, seed: int, widths: list, only=None, live_only: bool = False, oracle=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.ops import ssd, ssm

    T, Di, N, S, L = (shape[n] for n in ("T", "Di", "N", "S", "L"))
    dev = jax.devices()[0]
    rng = np.random.default_rng(seed)
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (T, Di))), jnp.float32)
    c = jnp.asarray(rng.normal(size=(T, Di)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, Di)) + 0.0
    D = jnp.ones((Di,), jnp.float32)
    starts, lens, position = _rows(rng, S, shape["chunk"], shape["ones"])
    qs, ql, kv = jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(position + lens)
    meta, blocks = ssd.block_plan(qs, ql, kv, T, ssm.CHUNK_TOKENS)
    planned = int(meta.shape[0])
    if live_only:
        meta = meta[:max(int(blocks), 1)]
    seg = ssm.token_segments(qs, ql, T)
    positions = jnp.asarray(np.concatenate(
        [np.arange(p, p + n) for p, n in zip(position, lens)] + [np.zeros(T - lens.sum(), np.int64)]).astype(np.int32))
    n_tokens = int(lens.sum())
    store = jnp.asarray(rng.normal(size=(L, S, N, Di)), jnp.float32)
    one, fresh = ql == 1, (kv - ql) == 0
    first = jnp.minimum(qs, T - 1)
    row = int(lens.argmax())
    at = slice(int(starts[row]), int(starts[row] + lens[row]))
    interpret = dev.platform != "tpu"

    def chain(launch):
        @jax.jit
        def chained(store_):
            def body(i, carry):
                store__, seen = carry
                y, store__ = launch(store__, i % L)
                return store__, seen + y[at.start]
            return jax.lax.fori_loop(0, CALLS, body, (store_, jnp.zeros((Di,), jnp.float32)))
        return chained

    def chunk(store_, o):
        return ssm.scan_chunk_tpu(store_, o, delta, c, B, C, A, D, meta, interpret=interpret)

    packed = lambda y, rows: jnp.zeros((T, Di), jnp.float32).at[jnp.where(rows, qs, T)].set(y, mode="drop")
    every = jnp.ones_like(one)   # a decode step: every slot advances

    def one_token(store_, o, rows=one):   # the plain form, over the layer's whole entry
        y, entry = ssm.scan_decode(store_[o], delta[first], c[first], B[first], C[first], A, D, rows, fresh)
        return packed(y, rows), jax.lax.dynamic_update_index_in_dim(store_, entry, o, 0)

    def one_token_kernel(width):
        def launch(store_, o, rows=one):
            ssm.DECODE_LANES = width     # read when the kernel is traced: the unjitted function, so every chain traces its own
            y, store_ = ssm.scan_decode_tpu.__wrapped__(store_, o, delta[first], c[first], B[first], C[first], A, D, rows,
                                                        fresh, interpret=interpret)
            return packed(y, rows), store_
        return launch

    def loop(store_, o):
        y, entry = ssm.scan_ragged(store_[o], delta, c, B, C, A, D, seg, positions, n_tokens)
        return y, jax.lax.dynamic_update_index_in_dim(store_, entry, o, 0)

    chunk_tokens, chunk_rows = int(lens[lens > 1].sum()), int((lens > 1).sum())
    chunk_bytes = chunk_tokens * (3 * Di + 2 * N * ssm.LANES) * 4 + chunk_rows * 2 * N * Di * 4
    units = chunk_tokens * Di // ssm.LANES
    line = {"shape": shape, "visits_live": float(blocks), "visits_planned": planned, "visits_handed": int(meta.shape[0]),
            "chunk_starts_at": at.start, "chunk_tokens": chunk_tokens,
            "chunk_bytes": chunk_bytes, "chunk_vector_ops": units * VECTOR_OPS, "chunk_exps": units * EXPS}
    forms = {"chunk_kernel": chunk, "one_token": one_token, "token_loop": loop,
             "decode_step": lambda s, o: one_token(s, o, every)}
    for u in widths:
        forms[f"one_token_kernel_w{u}"] = one_token_kernel(u)
        forms[f"decode_step_kernel_w{u}"] = functools.partial(one_token_kernel(u), rows=every)
    if only:
        forms, widths = {name: forms[name] for name in only}, [u for u in widths if f"one_token_kernel_w{u}" in only]
    for name, launch in forms.items():
        line[f"{name}_host_us"] = round(_timed(chain(launch), store, repeats), 1)
    if dev.platform == "tpu":
        chip = json.load(open(os.path.join(HERE, "bench", "chips.json")))[dev.device_kind]
        events = _events(lambda: jax.block_until_ready(chain(chunk)(store)), r"%?ssm_scan_chunk_kernel\b")
        if events:
            line["chunk_kernel_us"] = round(statistics.median(events), 1)
            line["chunk_kernel_us_min_max"] = [round(min(events), 1), round(max(events), 1)]
        for u in widths:
            for name in (f"one_token_kernel_w{u}", f"decode_step_kernel_w{u}"):
                events = _events(lambda: jax.block_until_ready(chain(forms[name])(store)), r"%?ssm_scan_decode_kernel\b")
                line[f"{name}_us"] = round(statistics.median(events), 1) if events else None
                line[f"{name}_us_min_max"] = [round(min(events), 1), round(max(events), 1)] if events else None
        # The whole chain's device time a launch (every operation but the loop that holds them): the forms like for like.
        for name in [n for n in ("one_token", "decode_step") if n in forms] + [
                f"{n}_kernel_w{u}" for u in widths for n in ("one_token", "decode_step")]:
            line[f"{name}_chain_device_us"] = round(sum(_events(
                lambda: jax.block_until_ready(chain(forms[name])(store)), r"(?!%?(?:while|conditional)\b)")) / CALLS, 1)
        line["one_token_rows"], line["decode_step_rows"] = int(one.sum()), S
        line["chunk_bytes_us"] = round(chunk_bytes / chip["hbm_bytes_per_s"] * 1e6, 1)
        line["chunk_vector_us"] = round(units * VECTOR_OPS / SLOTS_A_CYCLE / CLOCK_HZ * 1e6, 1)
        line["one_token_bytes_us"] = round(2 * S * N * Di * 4 / chip["hbm_bytes_per_s"] * 1e6, 1)   # the whole entry, as the plain form moves it
        if "token_loop" in forms:
            line["token_loop_us_a_trip"] = round(line["token_loop_host_us"] / n_tokens, 3)
    # Against the definition on this device: the chunk row's outputs and its slot's last state; a one-token row's too.
    want_y, want_s = jax.jit(lambda s: ssm.scan_ragged(s[1], delta, c, B, C, A, D, seg, positions, n_tokens))(store)
    got_y, got_s = jax.jit(lambda s: chunk(s, 1))(store)
    if oracle is not None:   # another checkout's launch on the same operands: the chunk row's outputs and the WHOLE store
        their_y, their_s = jax.jit(lambda s: oracle.scan_chunk_tpu(s, 1, delta, c, B, C, A, D, meta, interpret=interpret))(store)
        line["chunk_equal_to_the_oracle_bit_for_bit"] = {"y": bool(jnp.all(got_y[at] == their_y[at])),
                                                         "store": bool(jnp.all(got_s == their_s))}
    line["max_abs_diff_vs_recurrence"] = {
        "y": float(jnp.max(jnp.abs(got_y[at] - want_y[at]))), "state": float(jnp.max(jnp.abs(got_s[1, row] - want_s[row]))),
        "y_scale": float(jnp.max(jnp.abs(want_y[at])))}
    if only:
        return line
    y1, s1 = jax.jit(lambda s: one_token(s, 1))(store)
    yk, sk = jax.jit(lambda s: one_token_kernel(widths[0])(s, 1))(store)
    a_one = int(np.flatnonzero(lens == 1)[0])
    line["max_abs_diff_vs_recurrence"].update({
        "one_token_y": float(jnp.max(jnp.abs(y1[starts[a_one]] - want_y[starts[a_one]]))),
        "one_token_state": float(jnp.max(jnp.abs(s1[1, a_one] - want_s[a_one]))),
        "one_token_kernel_y": float(jnp.max(jnp.abs(jnp.where(one[:, None], yk[qs] - want_y[qs], 0.0)))),
        "one_token_kernel_state": float(jnp.max(jnp.abs(jnp.where(one[:, None, None], sk[1] - want_s, 0.0)))),
        "one_token_kernel_other_rows_and_layers_moved": bool(
            jnp.any(jnp.where(one[:, None, None], 0.0, sk[1] - store[1]) != 0) | jnp.any(sk[0] != store[0]))})
    return line


def attention(seed: int) -> dict:
    """Step 0 (a): the decode and the ragged kernel at 20 query heads over ONE KV
    head against the gather path, 32 rows of up to 100 pages of 128."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.ops import paged_attention as pa

    Hq, Hkv, Dh, page, S, maxp, T = 20, 1, 128, 128, 32, 100, 544
    rng = np.random.default_rng(seed)
    pool = lambda: jnp.asarray(rng.normal(size=(2 * S * maxp, page, Hkv * Dh)), jnp.bfloat16)
    k, v = pool(), pool()
    table = jnp.asarray(rng.permutation(S * maxp).reshape(S, maxp) + S * maxp, jnp.int32)   # the second layer's planes
    lengths = jnp.asarray(rng.integers(4096, 12700, S), jnp.int32).at[3].set(0)
    q = jnp.asarray(rng.normal(size=(S, Hq, Dh)), jnp.bfloat16)
    out = {"decode_plan": pa.decode_walk_plan(Hq, Hkv, Dh, page, maxp), "ragged_plan": pa.ragged_tile_plan(Hq, Hkv, Dh, page, maxp)}
    interpret = jax.devices()[0].platform != "tpu"
    got = jax.jit(lambda *a: pa.paged_attention_tpu(*a, Hkv, interpret=interpret))(q, k, v, table, lengths)
    want = jax.jit(lambda *a: pa.paged_attention_jax(*a, Hkv))(q, k, v, table, lengths)
    live = np.asarray(lengths) > 0
    out["decode_max_abs_diff"] = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))[live]))
    lens = np.ones(S, np.int32)
    lens[5], lens[3] = 512, 0
    lens[-1] = T - lens[:-1].sum() if T - lens[:-1].sum() > 0 else 1
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    kv = np.maximum(np.asarray(lengths), lens) * (lens > 0)
    qr = jnp.asarray(rng.normal(size=(T, Hq, Dh)), jnp.bfloat16)
    args = (qr, k, v, table, jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(kv, jnp.int32))
    got = jax.jit(lambda *a: pa.ragged_paged_attention_tpu(*a, Hkv, interpret=interpret))(*args)
    want = jax.jit(lambda *a: pa.ragged_paged_attention_jax(*a, Hkv))(*args)
    n = int(lens.sum())
    out["ragged_max_abs_diff"] = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))[:n]))
    out["scale"] = float(jnp.max(jnp.abs(want.astype(jnp.float32))[:n]))
    if not interpret:
        for name, fn, a in (("decode", lambda *a: pa.paged_attention_tpu(*a, Hkv), (q, k, v, table, lengths)),
                            ("ragged", lambda *a: pa.ragged_paged_attention_tpu(*a, Hkv), args)):
            jitted = jax.jit(fn)
            jax.block_until_ready(jitted(*a))
            ev = _events(lambda: jax.block_until_ready([jitted(*a) for _ in range(8)]), r"%?\w*attn")
            out[f"{name}_kernel_us"] = round(statistics.median(ev), 1) if ev else None
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", nargs="*", default=["jamba", "phi4flash"])
    ap.add_argument("--attention", action="store_true", help="step 0 (a): the paged kernels at 20 : 1")
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--seed", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true", help="a tiny shape, for a walk through on the CPU")
    ap.add_argument("--lanes", type=int, nargs="+", default=[1024], help="channels the one-token kernel advances at a time")
    ap.add_argument("--forms", nargs="+", help="time these forms alone (chunk_kernel, one_token, token_loop, ..)")
    ap.add_argument("--live-visits-only", action="store_true", help="hand the chunk kernel the plan cut to its live visits")
    ap.add_argument("--oracle-root", help="a checkout whose chunk kernel this one's has to equal to the bit")
    ap.add_argument("--tag", default="change")
    args = ap.parse_args()
    sys.path.insert(0, HERE)

    import jax

    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "calls_chained": CALLS}
    for name in (["tiny"] if args.rehearse else args.shape):
        out[name] = measure(SHAPES[name], 2 if args.rehearse else args.repeats, args.seed, args.lanes, args.forms,
                            args.live_visits_only, _load(args.oracle_root) if args.oracle_root else None)
        print(name, json.dumps(out[name]), flush=True)
    if args.attention and not args.rehearse:
        out["attention_20_to_1"] = attention(args.seed)
        print("attention", json.dumps(out["attention_20_to_1"]), flush=True)
    dest = os.path.join(HERE, "chiprun_out", "ssm_scan_bench")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"{args.tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    raise SystemExit(main())
