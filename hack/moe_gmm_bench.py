"""Time the two expert kernels alone on the chip (ISSUE 42).

By hand, through chiprun, parent and change in one call (a chip belongs to
one process at a time, so each side is a process of its own):

    chiprun -- sh -c 'python3 hack/moe_gmm_bench.py --root scratch_checkout/parent --profile; \
                      python3 hack/moe_gmm_bench.py --profile'

One layer's rows at each mixture cell's shape (experts, widths, top-k, the
step's tokens; which experts the rows choose from a seeded draw the script
prints), int8 stacks of two layers with the second read. Four programs a case:
`moe_gate_up_gmm` alone, `moe_down_gmm` alone (each chained CALLS times, the
host clock around `block_until_ready`, the median of REPEATS), everything
after the router (`_experts_of_shard`: sort, row gather, both kernels,
combine), so that a row layout's cost outside the kernels is counted, and the
combine alone (ISSUE 51: the gather of the down kernel's rows at a fixed
`back`, the select, the weights, the sum; the rows are the chain's carry, so
nothing is hoisted), with its us per million gathered elements. Beside
each time: the bytes of the experts with rows (matrices and scales, one
reading) and the v5e's least time for them (`bench/chips.json`), and the
largest absolute difference of the layer from the same layer through the
twin (`kernel=False`) and, with `--oracle-root`, through another checkout's
twin (a layout and a kernel this tree did not write). `--profile` adds the
kernels' own device time by operation. `--set NAME=JSON` sets a constant of `ops/moe.py` first, `--fn
NAME=JSON` replaces a function of one argument by a constant (the parent's
`_row_tile` / `_col_tile`; `"id"` is the function that returns its argument): a
variant to try.

Not part of the benchmark, imported by nothing a cell runs; a CPU run of it
times the CPU and is never a speed number.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CALLS, REPEATS, LAYERS = 16, 15, 2

# name: (tokens, experts, hidden, expert width, top-k, act, the draw: (experts never chosen, the favourite's odds))
CASES = {
    "smallthinker decode": (48, 64, 2560, 768, 6, "relu", (0, 1.0)),
    "smallthinker mixed": (560, 64, 2560, 768, 6, "relu", (0, 1.0)),
    "keye mixed": (524, 128, 2048, 768, 8, "silu", (0, 1.0)),
    # PERF.md section 6, PR 41: ~107 of 128 read, the most-used expert 10 x the mean
    "trinity mixed": (544, 128, 2048, 1024, 8, "silu", (21, 14.0)),
    # the cell's own kernels read as ~1.25 visits an expert (PERF.md section 5, PR 42): odds spread log-normally
    "trinity mixed broad": (544, 128, 2048, 1024, 8, "silu", (21, 1.0, 1.0)),
    "trinity decode": (32, 128, 2048, 1024, 8, "silu", (21, 14.0)),
    "keye decode": (12, 128, 2048, 768, 8, "silu", (0, 1.0)),
    # ISSUE 51: the top-10 and top-4 mixed steps and a top-4 decode step
    "granite mixed": (544, 72, 4096, 768, 10, "silu", (0, 1.0)),
    "xing mixed": (544, 64, 3584, 1024, 4, "silu", (0, 1.0)),
    "lfm2 decode": (64, 32, 2048, 1792, 4, "silu", (0, 1.0)),
}
# --rehearse: the control flow at a size the interpreter finishes (a mixed shape past 512 rows, a decode shape)
TINY = {
    "tiny mixed": (136, 8, 128, 256, 4, "silu", (2, 6.0)),
    "tiny decode": (6, 8, 128, 256, 4, "relu", (0, 1.0)),
}


def draw(name, tokens, experts, top_k, dead, favourite, sigma=0.0):
    """(tokens, experts) router logits whose top-k is the case's routing: a
    Gumbel draw over per-expert odds (``dead`` experts at none, expert 0 at
    ``favourite`` times the others', the others' spread log-normally by
    ``sigma``)."""
    import numpy as np

    rng = np.random.default_rng(42 + len(name))
    odds = np.exp(sigma * rng.normal(size=experts)) if sigma else np.ones(experts)
    odds[0] *= favourite
    if dead:
        odds[rng.permutation(np.arange(1, experts))[:dead]] = 1e-9
    return (np.log(odds)[None, :] + rng.gumbel(size=(tokens, experts))).astype(np.float32)


def _device_us_by_op(run, calls, most=4) -> dict:
    """Device time of one ``run()`` by operation, us a call, the ``most`` largest."""
    import glob
    import re
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            run()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        by_op: dict[str, float] = {}
        for plane in ProfileData.from_file(path).planes:
            if re.match(r"^/device:TPU:\d+$", plane.name):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for ev in line.events:
                            by_op[ev.name] = by_op.get(ev.name, 0.0) + ev.duration_ns / 1e3 / calls
    return {k[:48]: round(v, 2) for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:most]}


def _kernel_inputs(moe, x, expert, top_k, stacks, act):
    """What the module's own dispatch hands each kernel, and how to call it:
    ((rows, call gate/up, its stacks), (h, call down, its stack)). The stacks
    are arguments of every program (a closed-over stack is a constant of the
    executable: gigabytes, minutes a compile). The parent's rows lie compact and
    sorted; since ISSUE 42 the module lays them out itself (``dispatch``)."""
    import jax
    import jax.numpy as jnp

    wg, wu, wd = stacks
    E = wg.shape[1]
    layer = jnp.int32(LAYERS - 1)
    if hasattr(moe, "dispatch"):
        lay = moe.dispatch(expert, top_k, E)
        rows = x[lay.token]

        def gmm(act_):
            return lambda r, *ws: moe.grouped_matmul(r, ws, layer, lay.sizes, lay.plan, lay.row_expert, act=act_,
                                                     kernel=True)
    else:
        M = expert.shape[0]
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]
        row_expert = jnp.clip(expert[order], 0, E - 1)
        pad = -M % moe._row_tile(M)
        rows = jnp.pad(x[order // top_k], ((0, pad), (0, 0)))
        row_expert = jnp.pad(row_expert, (0, pad), constant_values=E - 1)

        def gmm(act_):
            return lambda r, *ws: moe.grouped_matmul(r, ws, layer, sizes, 0, row_expert, act=act_, kernel=True)
    up, down = gmm(act), gmm("silu")
    return (rows, up, (wg, wu)), (jax.jit(up)(rows, wg, wu), down, (wd,))


def _combine_inputs(moe, h, down, wd, expert, weights):
    """(y, the module's combine of it): the down kernel's rows and where each
    (token, choice) lies. A tree from before ISSUE 51 has no ``combine``: its
    four lines of ``_experts_of_shard`` stand here."""
    import jax
    import jax.numpy as jnp

    N, k = weights.shape
    lay = moe.dispatch(expert, k, wd.shape[1])
    y = jax.jit(down)(h, wd)
    if hasattr(moe, "combine"):
        return y, lambda y_: moe.combine(y_, lay.back, weights)

    def parents(y_):
        held = lay.back < lay.plan.rows
        per_choice = jnp.where(held[:, None], y_[jnp.minimum(lay.back, lay.plan.rows - 1)].astype(jnp.float32), 0.0)
        return jnp.einsum("nk,nkh->nh", weights, per_choice.reshape(N, k, -1))
    return y, parents


def measure(moe, cases, names, repeats, profile, oracle=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.ops.quant import QTensor

    dev = jax.devices()[0]
    chip = json.load(open(os.path.join(HERE, "bench", "chips.json"))).get(dev.device_kind)
    bf = jnp.bfloat16
    lines = {}
    for name, (T, E, H, I, k, act, routing) in cases.items():
        if names and name not in names:
            continue
        rng = np.random.default_rng(len(name))

        def stack(i, kk, n):  # drawn on the device: a host draw of 1.6 G int8 a case is minutes a process
            q = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(len(name)), i), (LAYERS, E, kk, n),
                                   -127, 128, jnp.int8)
            return QTensor(q, jnp.full((LAYERS, E, 1, n), 0.02 / 127 * (2048 / kk) ** 0.5, jnp.float32))

        stacks = (stack(0, H, I), stack(1, H, I), stack(2, I, H))
        x = jnp.asarray(rng.normal(size=(T, H)), bf)
        logits = jnp.asarray(draw(name, T, E, k, *routing))
        weights, idx = moe.router_topk(logits, k)
        expert = idx.reshape(-1).astype(jnp.int32)
        sizes = np.bincount(np.asarray(expert), minlength=E)
        read = int((sizes > 0).sum())
        nbytes = {"moe_gate_up_gmm": read * 2 * (H * I + 4 * I), "moe_down_gmm": read * (I * H + 4 * H)}
        line = {"rows": T * k, "experts": E, "experts_read": read, "rows_an_expert_mean": round(T * k / E, 1),
                "rows_most": int(sizes.max()), "sizes_head": sizes[:12].tolist()}

        def layer_fn(kernel):
            # the routing as something the rows decide (it is never nan): in the chained
            # program the sort and the layout run every call, as in a model's layer
            return lambda x_, *ws: moe._experts_of_shard(
                x_, weights, expert + (x_[0, 0] != x_[0, 0]).astype(jnp.int32), *ws, jnp.int32(LAYERS - 1),
                act=act, kernel=kernel, ep=None, tp=None)[0]

        got, want = jax.jit(layer_fn(True))(x, *stacks), jax.jit(layer_fn(False))(x, *stacks)
        line["max_abs_diff_vs_twin"] = float(jnp.max(jnp.abs(got - want)))
        if oracle is not None:  # another checkout's twin: a layout and a kernel this tree did not write
            theirs = jax.jit(lambda x_, *ws: oracle._experts_of_shard(
                x_, weights, expert, *ws, jnp.int32(LAYERS - 1), act=act, kernel=False, ep=None, tp=None)[0])(x, *stacks)
            line["max_abs_diff_vs_oracle_twin"] = float(jnp.max(jnp.abs(got - theirs)))
            line["twin_max_abs_diff_vs_oracle_twin"] = float(jnp.max(jnp.abs(want - theirs)))
        line["max_abs_twin"] = float(jnp.max(jnp.abs(want)))

        def timed(fn, arg, ws, calls=CALLS, most=4):
            """``fn`` chained ``calls`` times on ``arg`` (one element of the
            result is written back into the operand: a dependency, no more)."""
            @jax.jit
            def chained(a, *ws_):
                def body(_, a_):
                    return a_.at[0, 0].set(fn(a_, *ws_)[0, 0].astype(a_.dtype))
                return jax.lax.fori_loop(0, calls, body, a)

            jax.block_until_ready(chained(arg, *ws))
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(chained(arg, *ws))
                times.append((time.perf_counter() - t0) / calls)
            out = {"us_per_call": round(statistics.median(times) * 1e6, 1), "us_min": round(min(times) * 1e6, 1)}
            if profile:
                out["device_us_by_op"] = _device_us_by_op(lambda: jax.block_until_ready(chained(arg, *ws)), calls, most)
            return out

        (rows, up, pair), (h, down, one) = _kernel_inputs(moe, x, expert, k, stacks, act)
        line["rows_laid_out"] = int(rows.shape[0])
        for label, fn, arg, ws in (("moe_gate_up_gmm", up, rows, pair), ("moe_down_gmm", down, h, one)):
            got = timed(fn, arg, ws)
            got["mbytes"] = round(nbytes[label] / 1e6, 1)
            if chip is not None:
                least = nbytes[label] / chip["hbm_bytes_per_s"] * 1e6
                own = next((v for op, v in got.get("device_us_by_op", {}).items() if label in op), None)
                got.update(least_us=round(least, 1), share_of_bytes_pct=round(100 * least / got["us_per_call"], 1))
                if own:
                    got["device_us"], got["device_share_of_bytes_pct"] = own, round(100 * least / own, 1)
            line[label] = got
        line["after_router"] = timed(layer_fn(True), x, stacks, most=18)
        if hasattr(moe, "dispatch"):
            y, combine = _combine_inputs(moe, h, down, one[0], expert, weights)
            line["combine"] = timed(combine, y, (), most=8)
            line["combine"]["us_per_m_elements"] = round(line["combine"]["us_per_call"] / (T * k * H / 1e6), 2)
        if chip is not None:
            least = sum(nbytes.values()) / chip["hbm_bytes_per_s"] * 1e6
            line["after_router"].update(least_us=round(least, 1), share_of_bytes_pct=round(
                100 * least / line["after_router"]["us_per_call"], 1))
        if hasattr(moe, "visit_plan"):
            line["plan"] = {**moe.visit_plan(T * k, E)._asdict(), "col_blocks": [
                moe._col_block(H, I, 2, 1), moe._col_block(I, H, 1, 1)]}
        lines[name] = line
        print(name, json.dumps(line), flush=True)
    return lines


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE, help="checkout whose kernels to time")
    ap.add_argument("--cases", nargs="*")
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--rehearse", action="store_true", help="two tiny cases, for a walk through on the CPU")
    ap.add_argument("--set", nargs="*", default=[], metavar="NAME=JSON")
    ap.add_argument("--fn", nargs="*", default=[], metavar="NAME=JSON")
    ap.add_argument("--oracle-root", help="a checkout whose twin the layer is also compared with")
    ap.add_argument("--tag")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)

    import jax

    moe = importlib.import_module("inference_gateway_tpu.ops.moe")
    for item in args.set + args.fn:
        name, value = item.split("=", 1)
        assert hasattr(moe, name), name
        value = json.loads(value)
        if item in args.fn:  # "id": the function that returns its argument (a whole dimension)
            value = (lambda a: a) if value == "id" else (lambda v: lambda _arg: v)(value)
        setattr(moe, name, value)
    oracle = None
    if args.oracle_root:
        spec = importlib.util.spec_from_file_location(
            "oracle_moe", os.path.join(args.oracle_root, "inference_gateway_tpu", "ops", "moe.py"))
        oracle = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(oracle)
    dev = jax.devices()[0]
    out = {"root": os.path.relpath(root, HERE), "set": args.set, "fn": args.fn,
           "device": {"platform": dev.platform, "kind": dev.device_kind}, "calls_chained": CALLS,
           "cases": measure(moe, TINY if args.rehearse else CASES, args.cases, args.repeats, args.profile, oracle)}
    dest = os.path.join(HERE, "chiprun_out", "moe_gmm_bench")
    os.makedirs(dest, exist_ok=True)
    tag = args.tag or ("change" if root == HERE else os.path.basename(root))
    with open(os.path.join(dest, f"{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    raise SystemExit(main())
