"""Time the one-token state updates alone on the chip (ISSUE 63):
`ops/ssd.update_store`'s kernel `ssm_state_update_kernel` (Mamba-2) and
`ops/kda.update_store`'s `kda_state_update_kernel` (KDA).

By hand, through chiprun, parent and change in one call (a chip belongs to one
process at a time, so each side is a process of its own):

    chiprun -- sh -c 'python3 hack/state_update_bench.py --root scratch_checkout/parent; \
                      python3 hack/state_update_bench.py'

One launch a case at the cells' shapes: Granite's store `f32[9, 32, 128, 64, 128]`
and Kimi's `f32[6, 32, 32, 128, 128]` with 8, 15 and 31 of the 32 slots advancing
(a seeded draw of slots, one of them `fresh`), 15 of 15 (all of a smaller store:
the same live bytes as 15 of 32) and, for Granite's, ROADMAP W9's 64 slots all
advancing. The launch is chained CALLS times in one program, the layer ordinal
going round the store's entries and the store carried in place; the time is the
kernel's own events in a capture of one such program, by the kernel's name (what
the cells' `ssd_state_hbm_roofline_share` / `kda_state_hbm_roofline_share` divide
by), their median. Beside it the bytes the advancing rows' states are, once in
and once out, and their time at the chip's bandwidth (`bench/chips.json`).

`--set NAME=VALUE` sets a constant of the timed module first (the head block of
the kernels as they were: `ssd.HEAD_BLOCK=16`; a visit's bytes of the kernels as they
are: `ssd.VISIT_BYTES=524288`, which `ops/kda.py` reads there too); `--ablate turns` times a copy of the
module with the lanes-to-sublanes turns cut out (wrong answers, a right time: the
replacements below are written against the kernels as they were before ISSUE 63
and say so where they no longer apply); `--file` times any other copy. The check
that rides along is the largest difference from this checkout's `state_update` on
the same device, and whether the rows that do not advance kept their bits.

Not part of the benchmark, imported by nothing a cell runs; a CPU run of it
(`--rehearse`: tiny shapes through the interpreted kernels) times the CPU and is
never a speed number.
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

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CALLS = 18   # a multiple of both stores' layers

# name: (which module, state layers, slots, heads, rows of a head's state, its lanes, slots advancing)
CASES = {
    **{f"granite {n}/32": ("ssd", 9, 32, 128, 64, 128, n) for n in (8, 15, 31)},
    "granite 15/15": ("ssd", 9, 15, 128, 64, 128, 15),
    "granite 64/64": ("ssd", 9, 64, 128, 64, 128, 64),
    **{f"kimi {n}/32": ("kda", 6, 32, 32, 128, 128, n) for n in (8, 15, 31)},
    "kimi 15/15": ("kda", 6, 15, 32, 128, 128, 15),
}
TINY = {"tiny ssd 3/6": ("ssd", 2, 6, 4, 32, 128, 3), "tiny kda 3/6": ("kda", 2, 6, 4, 128, 128, 3)}
KERNEL = {"ssd": "ssm_state_update_kernel", "kda": "kda_state_update_kernel"}

# The turns of the kernels as they stood at PR 62, cut out one for one (the arithmetic is then WRONG).
TURNS = {
    "ssd": [("x_col = jnp.broadcast_to(x_ref[...], (128, hb * P)).T[:, :N]",
             "x_col = jnp.broadcast_to(x_ref[...][:, :N], (hb * P, N))"),
            ("y_ref[...] = jnp.broadcast_to(y_col, (hb * P, 128)).T[:1, :]",
             "y_ref[...] = jnp.full((1, hb * P), jnp.sum(y_col))")],
    "kda": [("col = lambda row: jnp.broadcast_to(row, (K, K)).T", "col = lambda row: jnp.broadcast_to(row, (K, K))")],
}


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _without_turns(path: str, which: str, dest: str) -> str | None:
    text = open(path).read()
    for old, new in TURNS[which]:
        if old not in text:
            return None
        text = text.replace(old, new)
    out = os.path.join(dest, f"{which}_without_turns.py")
    with open(out, "w") as f:
        f.write(text)
    return out


def _kernel_us(run, name: str) -> dict:
    """One ``run()`` under the profiler: the kernel's events by name, us."""
    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            run()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        events = [ev.duration_ns / 1e3
                  for plane in ProfileData.from_file(path).planes if plane.name.startswith("/device:TPU:")
                  for line in plane.lines if line.name == "XLA Ops"
                  for ev in line.events if name in ev.name]
    if not events:
        return {"kernel_events": 0}
    return {"kernel_events": len(events), "kernel_us": round(statistics.median(events), 2),
            "kernel_us_min": round(min(events), 2), "kernel_us_max": round(max(events), 2)}


def _operands(which: str, rng, S: int, Hh: int, R: int, N: int):
    """A launch's operands beside the store, as ``update_store`` takes them."""
    import jax.numpy as jnp
    import numpy as np

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    if which == "ssd":
        dt = np.log1p(np.exp(rng.normal(size=(S, Hh)) - 3.0))
        return (f32(dt), f32(rng.normal(size=(S, Hh, R))), f32(rng.normal(size=(S, N))), f32(rng.normal(size=(S, N))),
                f32(-np.exp(rng.normal(size=(Hh,)))), f32(rng.normal(size=(Hh,))))
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    return (f32(unit(rng.normal(size=(S, Hh, R))) * R ** -0.5), f32(unit(rng.normal(size=(S, Hh, R)))),
            f32(rng.normal(size=(S, Hh, N))), f32(-np.exp(rng.normal(size=(S, Hh, R)) * 1.5 - 4.0)),
            f32(1 / (1 + np.exp(-rng.normal(size=(S, Hh))))))


def measure(module, twin, name: str, case: tuple, seed: int, bandwidth: float | None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    which, L, S, Hh, R, N, live = case
    rng = np.random.default_rng(seed)
    operands = _operands(which, rng, S, Hh, R, N)
    advancing = np.sort(rng.choice(S, live, replace=False))
    valid = np.zeros(S, bool)
    valid[advancing] = True
    fresh = np.zeros(S, bool)
    fresh[advancing[live // 2]] = True
    valid, fresh = jnp.asarray(valid), jnp.asarray(fresh)
    store = jax.random.normal(jax.random.PRNGKey(seed), (L, S, Hh, R, N), jnp.float32)

    @jax.jit
    def chained(store_):
        def body(i, carry):
            store__, seen = carry
            out, store__ = module.update_store(store__, i % L, *operands, valid, fresh, kernel=True)
            return store__, seen + out[advancing[0]]
        return jax.lax.fori_loop(0, CALLS, body, (store_, jnp.zeros((Hh, R))))

    jax.block_until_ready(chained(store))
    state_bytes = live * 2 * Hh * R * N * 4
    line = {"case": name, "store": [L, S, Hh, R, N], "advancing": live, "state_bytes_a_launch": state_bytes}
    if bandwidth:
        line["bytes_us"] = round(state_bytes / bandwidth * 1e6, 2)
        line.update(_kernel_us(lambda: jax.block_until_ready(chained(store)), KERNEL[which]))
        if line.get("kernel_us"):
            line["share_of_bytes_pct"] = round(100 * line["bytes_us"] / line["kernel_us"], 2)
    # Against this checkout's definition on this device, entry 1 of the store.
    got_o, got_s = jax.jit(lambda s: module.update_store(s, 1, *operands, valid, fresh, kernel=True))(store)
    want_o, want_s = jax.jit(lambda s: twin.update_store(s, 1, *operands, valid, fresh, kernel=False))(store)
    idle = np.setdiff1d(np.arange(S), advancing)
    line["max_abs_diff_vs_definition"] = {
        "out": float(jnp.max(jnp.abs(got_o[advancing] - want_o[advancing]))), "out_scale": float(jnp.max(jnp.abs(want_o[advancing]))),
        "state": float(jnp.max(jnp.abs(got_s[1, advancing] - want_s[1, advancing]))),
        "idle_rows_and_other_entries_kept_their_bits":
            bool(jnp.array_equal(got_s[1, idle], store[1, idle])) and bool(jnp.array_equal(got_s[0], store[0]))}
    return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE, help="checkout whose ops/ssd.py and ops/kda.py to time")
    ap.add_argument("--file", action="append", default=[], metavar="ssd=PATH", help="a copy of a module to time in its place")
    ap.add_argument("--set", action="append", default=[], metavar="ssd.NAME=JSON", help="a constant of a timed module")
    ap.add_argument("--ablate", choices=["turns"], help="time copies of the modules with a part cut out")
    ap.add_argument("--cases", nargs="*", help="substrings of the cases' names (default: all)")
    ap.add_argument("--seed", type=int, default=63)
    ap.add_argument("--rehearse", action="store_true", help="tiny shapes, for a walk through on the CPU")
    ap.add_argument("--tag")
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    root = os.path.abspath(args.root)
    dest = os.path.join(HERE, "chiprun_out", "state_update_bench")
    os.makedirs(dest, exist_ok=True)

    import jax

    from inference_gateway_tpu.ops import kda as here_kda
    from inference_gateway_tpu.ops import ssd as here_ssd

    dev = jax.devices()[0]
    paths = {w: os.path.join(root, "inference_gateway_tpu", "ops", f"{w}.py") for w in ("ssd", "kda")}
    paths.update(dict(f.split("=", 1) for f in args.file))
    if args.ablate == "turns":
        paths = {w: _without_turns(p, w, dest) for w, p in paths.items()}
    modules = {w: _load(os.path.abspath(p), f"timed_{w}") for w, p in paths.items() if p}
    for item in args.set:
        target, value = item.split("=", 1)
        which, const = target.split(".", 1)
        setattr(modules[which], const, json.loads(value))
        if which == "ssd" and hasattr(here_ssd, const):   # what ``ops/kda.py`` takes from ``ops/ssd.py`` it reads there
            setattr(here_ssd, const, json.loads(value))
    bandwidth = None
    if dev.platform == "tpu":
        bandwidth = json.load(open(os.path.join(HERE, "bench", "chips.json")))[dev.device_kind]["hbm_bytes_per_s"]
    tag = args.tag or ("change" if root == HERE and not (args.file or args.set or args.ablate) else
                       "-".join([os.path.basename(root)] + ([args.ablate] if args.ablate else []) + args.set))
    cases = TINY if args.rehearse else CASES
    lines = []
    for name, case in cases.items():
        if args.cases and not any(c in name for c in args.cases):
            continue
        if case[0] not in modules:
            lines.append({"case": name, "skipped": "the ablation's replacements do not apply to this tree's kernel"})
        else:
            try:
                lines.append(measure(modules[case[0]], {"ssd": here_ssd, "kda": here_kda}[case[0]], name, case,
                                     args.seed, bandwidth))
            except Exception as e:   # a variant the compiler refuses is a line of the table, not the end of it
                lines.append({"case": name, "failed": f"{type(e).__name__}: {str(e)[:400]}"})
        print(tag, json.dumps(lines[-1]), flush=True)
    out = {"tag": tag, "root": os.path.relpath(root, HERE), "device": {"platform": dev.platform, "kind": dev.device_kind},
           "calls_chained": CALLS, "set": args.set, "ablate": args.ablate, "lines": lines}
    with open(os.path.join(dest, f"{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    raise SystemExit(main())
