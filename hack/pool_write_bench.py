"""Time a layer's pool writes alone on the chip (ISSUE 62).

By hand, through chiprun:

    chiprun -- python3 hack/pool_write_bench.py [--cases NAME ...] [--set NAME=JSON ...]

One step's rows into both flat pools at each cell's shape (rows x row bytes, the
decode rows a row each in a page of their own, the prompt chunk's rows back to
back from the middle of a page over the pages of a seeded draw, a padded tail
dropped), three candidates: `models/llama.py`'s scatter as it was
(`pool.at[idx].set(rows, mode="drop")`), the same scatter told
`unique_indices=True` (dropped rows given distinct indices past the pool, so the
promise is true), and `ops/pool_write.py`'s run-copy kernel. Each is chained
over LAYERS planes of the pools by a `lax.scan` whose carry the pools are, as
the step programs do, so nothing is hoisted; the time is the device's, the
program's own event on the profiler's module line over LAYERS, with the largest
operations beside it, the median of REPEATS. Every candidate's pools are held to
the first's bit for bit. `--set NAME=JSON` sets a constant of `ops/pool_write.py`
first: a variant to try; `--only runs` times the kernel alone.

Not part of the benchmark, imported by nothing a cell runs; a CPU run of it
(`--rehearse`: tiny shapes, the kernel interpreted) times the CPU and is never a
speed number.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, REPEATS, PAGE = 16, 5, 128

# name: (rows of the step, of which decode rows, of which a dropped tail, lanes of a row, pages of a plane)
CASES = {
    "evabyte mixed": (517, 5, 0, 4096, 105),
    "mistral mixed": (536, 24, 0, 1024, 384),
    "qwen2.5 mixed": (560, 48, 0, 512, 768),
    "trinity mixed": (544, 32, 0, 512, 1024),
    "lfm2 mixed": (576, 64, 0, 512, 1024),
    "mistral mixed half empty": (536, 24, 256, 1024, 384),
    "evabyte decode": (5, 5, 0, 4096, 105),
    "mistral decode": (24, 24, 0, 1024, 384),
    "qwen2.5 decode": (48, 48, 0, 512, 768),
    "lfm2 decode": (64, 64, 0, 512, 1024),
    "lfm2 state layer": (576, 0, 576, 512, 1024),
}
TINY = {"tiny mixed": (40, 4, 3, 128, 12), "tiny decode": (4, 4, 0, 128, 12), "tiny skipped": (40, 0, 40, 128, 12)}


def draw(name: str, rows: int, decode: int, dropped: int, pages: int, page: int):
    """The step's indices into one plane: ``decode`` single rows, each at a place of its
    own page, then the chunk's rows from the middle of a page on over pages that lie
    anywhere, then ``dropped`` rows whose index is the plane's row count."""
    import zlib

    import numpy as np

    rng = np.random.default_rng(zlib.crc32(name.encode()))
    order = rng.permutation(pages)
    singles = order[:decode] * page + rng.integers(0, page, decode)
    chunk = rows - decode - dropped
    start = int(rng.integers(1, page))
    n_pages = -(-(start + chunk) // page)
    at = np.concatenate([p * page + np.arange(page) for p in order[decode:decode + n_pages]])[start:start + chunk]
    return np.concatenate([singles, at, np.full(dropped, pages * page)]).astype(np.int32)


def _device_us(run, most=3):
    """(device us of the program's own event, the largest operations' us) of one ``run()``."""
    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            run()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        module, by_op = 0.0, {}
        for plane in ProfileData.from_file(path).planes:
            if re.match(r"^/device:TPU:\d+$", plane.name):
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        module += sum(ev.duration_ns for ev in line.events) / 1e3
                    elif line.name == "XLA Ops":
                        for ev in line.events:
                            if not re.match(r"%?while", ev.name):
                                by_op[ev.name] = by_op.get(ev.name, 0.0) + ev.duration_ns / 1e3
    return module, {k[:56]: round(v / LAYERS, 2) for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:most]}


def measure(pw, cases: dict, names, repeats: int, page: int, interpret: bool, only=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    def candidates(flat, total):
        def plane_idx(idx, li):
            return jnp.where(idx >= flat, total, idx + li * flat)

        def scatter(pools, rows, w):
            return tuple(p.at[w].set(r, mode="drop") for p, r in zip(pools, rows))

        def hinted(pools, rows, w):
            w = jnp.where(w >= total, total + jnp.arange(w.shape[0]), w)
            return tuple(p.at[w].set(r, mode="drop", unique_indices=True) for p, r in zip(pools, rows))

        def runs(pools, rows, w):
            return pw.write_rows_tpu(pools, rows, w, interpret=interpret)

        def chained(write):
            def program(pk, pv, rk, rv, idx):
                def layer(pools, li):
                    with jax.named_scope("kv.write"):
                        return write(pools, (rk, rv), plane_idx(idx, li)), None
                return jax.lax.scan(layer, (pk, pv), jnp.arange(LAYERS))[0]
            return jax.jit(program, donate_argnums=(0, 1))
        return {"scatter": chained(scatter), "scatter unique_indices": chained(hinted), "runs": chained(runs)}

    out = {}
    for name, (rows, decode, dropped, width, pages) in cases.items():
        if names and name not in names:
            continue
        flat = pages * page
        total = LAYERS * flat
        idx = jnp.asarray(draw(name, rows, decode, dropped, pages, page))
        key = jax.random.key(rows * 7 + width)
        rk, rv = (jax.random.normal(k, (rows, width), jnp.float32).astype(jnp.bfloat16) for k in jax.random.split(key))
        fresh = lambda: tuple(jnp.full((total, width), v, jnp.bfloat16) for v in (1.0, -1.0))
        case = {"rows": rows, "row_bytes": 2 * width, "decode_rows": decode, "dropped": dropped,
                "path": pw.pool_write_dispatch(rows, width, jnp.bfloat16, total)[0]}
        first = None
        for label, program in candidates(flat, total).items():
            if only and label not in only:
                continue
            got = jax.block_until_ready(program(*fresh(), rk, rv, idx))
            digest = [np.asarray(jax.lax.bitcast_convert_type(g, jnp.uint16)[::7].astype(jnp.uint32).sum()) for g in got]
            if first is None:
                first = got
                same = True
            else:
                same = all(bool(jnp.array_equal(jax.lax.bitcast_convert_type(a, jnp.uint16),
                                                jax.lax.bitcast_convert_type(b, jnp.uint16))) for a, b in zip(first, got))
            del got
            times, ops = [], {}
            for _ in range(repeats):
                pools = jax.block_until_ready(fresh())
                module, ops = _device_us(lambda: jax.block_until_ready(program(*pools, rk, rv, idx)))
                times.append(module / LAYERS)
            case[label] = {"us_a_layer": round(statistics.median(times), 2), "spread_us": round(max(times) - min(times), 2),
                           "equal_to_scatter": same, "digest": [int(d) for d in digest], "largest_ops_us_a_layer": ops}
            print(f"{name:28s} {label:24s} {case[label]['us_a_layer']:9.2f} us a layer  equal={same}", flush=True)
        del first
        out[name] = case
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", nargs="*")
    ap.add_argument("--only", nargs="*", help="candidates to time (the first one timed is what the others are held to)")
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--rehearse", action="store_true", help="tiny cases, for a walk through on the CPU")
    ap.add_argument("--set", nargs="*", default=[], metavar="NAME=JSON")
    ap.add_argument("--tag", default="change")
    args = ap.parse_args()
    sys.path.insert(0, HERE)

    import jax

    from inference_gateway_tpu.ops import pool_write as pw

    for item in args.set:
        name, value = item.split("=", 1)
        assert hasattr(pw, name), name
        setattr(pw, name, json.loads(value))
    dev = jax.devices()[0]
    out = {"set": args.set, "device": {"platform": dev.platform, "kind": dev.device_kind}, "layers_chained": LAYERS,
           "cases": measure(pw, TINY if args.rehearse else CASES, args.cases, args.repeats,
                            8 if args.rehearse else PAGE, interpret=dev.platform != "tpu", only=args.only)}
    dest = os.path.join(HERE, "chiprun_out", "pool_write_bench")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"{args.tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    raise SystemExit(main())
