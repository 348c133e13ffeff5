"""Time the paged attention kernels alone on the chip (ISSUE 34, ISSUE 37, ISSUE 44).

By hand, through chiprun, parent and change in one call (a chip belongs to
one process at a time, so each side is a process of its own):

    chiprun -- sh -c 'python3 hack/ragged_attention_bench.py --root scratch_checkout/parent; \
                      python3 hack/ragged_attention_bench.py'

One mixed step's rows at each benchmark cell's shape (heads, window, slots,
the step's width; contexts and chunk lengths as the cell's traffic gives
them), `ragged_paged_attention_tpu` chained CALLS times inside one program as
a step's layers chain it, the host clock around `block_until_ready`, the
median of REPEATS. Beside the time: the call's arithmetic and the bytes of one
reading of what its rows can see, the v5e's least time for them
(`bench/chips.json`), and the largest absolute difference from
`ragged_paged_attention_jax` on the same bf16 inputs. `--set NAME=JSON` sets a
constant of the kernel's module first (a variant to try); `--window-operand`
hands the cases of a model that mixes layer kinds their window as the operand
its mixed step hands it. ISSUE 44's levers by `--set`: `RAGGED_VISIT_BYTES=1`
walks a wide tile one page a visit (`=9437184` two, `=16777216` four at
Trinity-Mini's shape), `RAGGED_VISITS_A_ROW=1` lifts the cap that short rows
put on it, `RAGGED_UNMASKED_VISITS=false` masks every visit; the one-query
rows' path has no switch (the parent's tree is its "off": `--root`), and the
`.chunk` / `.rows` cases hold each half of a step alone.

The long-context cases (ISSUE 44, ``trinity-mini.long-context.*``) hold rows
of up to 100 pages, a windowed and a global layer's call each whole, with the
chunk alone and with the one-query rows alone; their twin is
``ragged_paged_attention_jax`` called a row and 64 queries at a time (whole, it
gathers every query's own copy of its row's keys: 7 GB here).

The decode-only cases (ISSUE 37, ``decode:<cell>``) hold one decode step's
slots at each cell's shape, live and empty, and time two kernels on the same
rows: ``paged_attention_tpu`` (the decode kernel every decode program calls)
and the ragged kernel's one-query path (each live slot a row of one query).

The learned sparse attention's one-query rows (ISSUE 45, ``keye-one-query:<context>/<pages>``):
12 rows at Keye-VL-2.0's layout (Hq 32, Hkv 4, D 128, an indexer of 16 heads
of 64, ``topk`` 2,048), every row at the case's context, on the cell's
104-page table up to 12.7k and on a 256-page one above; ``sparse_decode``
whole (scores, selection, read) with its read forced each way through
``WALK_UNDER``: the token gather behind ``lax.top_k``, and the masked walk
of whole pages behind the exact k-th score. The crossover sets the constant.

Not part of the benchmark, imported by nothing a cell runs; a CPU run of it
times the CPU and is never a speed number.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, MAX_PAGES, CALLS, REPEATS = 128, 16, 16, 15
# A decode-only call is 50-500 us: chained 16 times, the host's ~0.5 ms to dispatch a
# program and hear of its end would be a third of the reading (ISSUE 37, step 0).
DECODE_CALLS = 128

# name: (Hq, Hkv, D, window, slots, step width, [(q_len, kv_len), ...]), and
# for the long-context cases the pages a row may hold (MAX_PAGES otherwise)
def _cases() -> dict:
    import numpy as np

    rng = np.random.default_rng(33)

    def decode(n, lo, hi):
        return [(1, int(k)) for k in rng.integers(lo, hi, size=n)]

    # every step of trinity-mini.long-context: a 512-token chunk deep in a
    # 4,096-12,288-token prompt beside ~17 one-query rows at 4,200-12,700
    long_rows = [(1, int(k)) for k in np.random.default_rng(44).integers(4200, 12700, size=17)]
    long_chunk = [(512, 8704)]

    def trinity(window, rows):
        return (32, 4, 128, window, 32, 544, rows, 100)

    return {
        "trinity-mini.long-context.window": trinity(2048, long_chunk + long_rows),
        "trinity-mini.long-context.window.chunk": trinity(2048, long_chunk),
        "trinity-mini.long-context.window.rows": trinity(2048, long_rows),
        "trinity-mini.long-context.global": trinity(None, long_chunk + long_rows),
        "trinity-mini.long-context.global.chunk": trinity(None, long_chunk),
        "trinity-mini.long-context.global.rows": trinity(None, long_rows),
        # a 512-token chunk of a 1,024-1,792-token prompt, ~2.5 rows decoding
        "mistral-7b.long-prompt": (32, 8, 128, 4096, 24, 536,
                                   [(512, 1024)] + decode(3, 1100, 1800)),
        # 48 slots never short of admissions: ~440 prompt tokens a step
        "qwen2.5-7b.batch": (28, 4, 128, None, 48, 560,
                             [(300, 300), (140, 140)] + decode(46, 100, 900)),
        "smallthinker-21b-a3b.batch": (28, 4, 128, 4096, 48, 560,
                                       [(300, 300), (140, 140)] + decode(46, 100, 900)),
        # one ~280-token prompt over the rows a 4-5 req/s open loop keeps
        "qwen2.5-7b.chat": (28, 4, 128, None, 48, 560, [(280, 280)] + decode(14, 100, 700)),
        "mistral-7b.chat": (32, 8, 128, 4096, 24, 536, [(280, 280)] + decode(10, 100, 700)),
        # 64 rows mostly past the 512-token window, one short prompt
        "phi-4-mini-flash.reason": (40, 10, 128, 512, 64, 576,
                                    [(190, 190)] + decode(62, 300, 1500)),
        # the shape an 8-query tile was worst at, alone: a chunk deep in a prompt
        "chunk-512-at-1536": (32, 8, 128, 4096, 24, 536, [(512, 1536)]),
        # what no cell sends often: the last few tokens of three chunked prompts
        # (a wide tile of which 2-20 queries are live) beside the decoding rows
        "chunk-tails": (28, 4, 128, None, 48, 560,
                        [(5, 517), (20, 1044), (2, 300)] + decode(45, 100, 900)),
    }


# name: (Hq, Hkv, D, window, slots, live slots' contexts): one decode step's
# slots, the live ones at the contexts of PERF.md section 4's traffic.
def _decode_cases() -> dict:
    import numpy as np

    rng = np.random.default_rng(37)

    def ctx(n, lo, hi):
        return [int(k) for k in rng.integers(lo, hi, size=n)]

    batch, reason = ctx(48, 100, 900), ctx(64, 300, 1500)
    return {
        "decode:qwen2.5-7b.batch": (28, 4, 128, None, 48, batch),
        "decode:qwen2.5-7b.chat": (28, 4, 128, None, 48, ctx(15, 100, 700)),
        "decode:mistral-7b.chat": (32, 8, 128, 4096, 24, ctx(11, 100, 700)),
        "decode:mistral-7b.long-prompt": (32, 8, 128, 4096, 24, ctx(3, 1100, 1800)),
        # the mixture's two kinds of layer: contexts under the window, a mask apart
        "decode:smallthinker-21b-a3b.batch.window": (28, 4, 128, 4096, 48, batch),
        "decode:smallthinker-21b-a3b.batch.global": (28, 4, 128, None, 48, batch),
        # the hybrid's: most rows past the 512-token window, and the full layers
        "decode:phi-4-mini-flash.reason.window": (40, 10, 128, 512, 64, reason),
        "decode:phi-4-mini-flash.reason.full": (40, 10, 128, None, 64, reason),
    }


# (context, pages a row of the table holds). The cell's rows end at 12,800 on 104 pages; the
# last two are FULL rows of 9 x and 12 x topk, the walk at its worst for the table.
KEYE_ONE_QUERY = ((4096, 104), (8192, 104), (12700, 104), (16384, 256), (24576, 256), (32700, 256),
                  (18432, 144), (24576, 192))
KEYE = dict(B=12, Hq=32, Hkv=4, D=128, Hi=16, Di=64, topk=2048)


# The cases whose model mixes windowed and global layers.
MIXED_KINDS = ("smallthinker-21b-a3b.batch", "phi-4-mini-flash.reason", "trinity-mini.long-context")


def _reckon(rows, Hq, Hkv, D, window) -> tuple[float, float]:
    """(floating-point operations, bytes) the call needs: 4·Hq·D a visible
    (query, key) pair; each page a row can see read once, q read and the
    output written once."""
    pairs = pages = tokens = 0
    for q_len, kv_len in rows:
        start = kv_len - q_len
        for j in range(q_len):
            pos = start + j
            pairs += min(pos + 1, window or pos + 1)
        first = max(start + 1 - (window or kv_len), 0) // PAGE
        pages += -(-kv_len // PAGE) - first
        tokens += q_len
    return 4.0 * Hq * D * pairs, 2.0 * pages * PAGE * Hkv * D * 2 + 2.0 * tokens * Hq * D * 2


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE, help="checkout whose kernel to time")
    ap.add_argument("--cases", nargs="*", help="ragged cases by name, decode-only cases by prefix ('decode:')")
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--profile", action="store_true",
                    help="one more run of each chained program under the profiler: device us a call by operation")
    ap.add_argument("--set", nargs="*", default=[], metavar="NAME=JSON")
    ap.add_argument("--window-operand", action="store_true")
    ap.add_argument("--tag")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)

    import jax

    pa = importlib.import_module("inference_gateway_tpu.ops.paged_attention")
    for item in args.set:
        name, value = item.split("=", 1)
        assert hasattr(pa, name), name
        setattr(pa, name, json.loads(value))
    dev = jax.devices()[0]
    cases = measure(pa, args.cases, args.window_operand, args.repeats, args.profile)
    cases.update(measure_keye(importlib.import_module("inference_gateway_tpu.ops.sparse_attention"),
                              args.cases, args.repeats, args.profile))
    out = {"root": os.path.relpath(root, HERE), "set": args.set, "window_operand": args.window_operand,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "calls_chained": CALLS, "decode_calls_chained": DECODE_CALLS, "cases": cases}
    dest = os.path.join(HERE, "chiprun_out", "ragged_attention_bench")
    os.makedirs(dest, exist_ok=True)
    tag = args.tag or ("change" if root == HERE else os.path.basename(root))
    with open(os.path.join(dest, f"{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if dev.platform == "tpu" else 1


def _timed(call, q, k, v, ref, rows, Hq, Hkv, D, window, chip, repeats, live=True, profile=False,
           calls=CALLS, operands=()) -> dict:
    """One kernel's line: ``call(q, k, v, *operands)`` chained ``calls`` times
    in one program (the table and the descriptors its ARGUMENTS, as a step
    program holds them: closed over, what a call derives from them would be
    folded away); its distance from ``ref`` over the rows of ``live``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chained(q_, k_, v_, *ops):
        return jax.lax.fori_loop(0, calls, lambda i, x: call(x, k_, v_, *ops), q_)

    got = jax.block_until_ready(jax.jit(call)(q, k, v, *operands))
    diff = float(jnp.max(jnp.where(live, jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)), 0)))
    times = _clock(lambda: chained(q, k, v, *operands), repeats, calls)
    flops, nbytes = _reckon(rows, Hq, Hkv, D, window)
    line = {"us_per_call": round(statistics.median(times) * 1e6, 1),
            "us_min": round(min(times) * 1e6, 1),
            "gflop": round(flops / 1e9, 3), "mbytes": round(nbytes / 1e6, 2),
            "max_abs_diff_vs_twin_bf16": diff,
            "rows": len(rows), "prompt_tokens": sum(ql for ql, _ in rows if ql > 1)}
    if chip is not None:
        t_flop = flops / chip["bf16_flops_per_s"]
        t_byte = nbytes / chip["hbm_bytes_per_s"]
        least = max(t_flop, t_byte)
        line.update(least_us=round(least * 1e6, 2),
                    bound="flops" if t_flop >= t_byte else "bytes",
                    roofline_share_pct=round(100 * least / statistics.median(times), 2))
    if profile:
        line["device_us_by_op"] = _device_us_by_op(
            lambda: jax.block_until_ready(chained(q, k, v, *operands)), calls)
    return line


def _clock(run, repeats, calls) -> list:
    """Seconds a call of ``run()`` (``calls`` chained calls), ``repeats`` times after one that warms it."""
    import jax

    jax.block_until_ready(run())
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append((time.perf_counter() - t0) / calls)
    return times


def _device_us_by_op(run, calls, top=6) -> dict:
    """Device time of one ``run()`` (``calls`` chained calls) by operation, us
    a call, the ``top`` largest and their sum beside the whole: the profiler's
    ``XLA Ops`` line of the device."""
    import glob
    import re
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            run()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        data = ProfileData.from_file(path)
        by_op: dict[str, float] = {}
        for plane in data.planes:
            if re.match(r"^/device:TPU:\d+$", plane.name):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for ev in line.events:
                            by_op[ev.name] = by_op.get(ev.name, 0.0) + ev.duration_ns / 1e3 / calls
    # A loop's event spans its body's: the whole is the operations that are no loop.
    whole = sum(v for k, v in by_op.items() if not k.lstrip("%").startswith("while"))
    return {**{k[:60]: round(v, 2) for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]},
            "all_but_loops": round(whole, 2)}


def _twin(pa, q, k, v, pt, q_starts, q_lens, kv_lens, Hkv, window, whole=True, block=64):
    """``ragged_paged_attention_jax`` on the case; a case of deep rows a row
    and ``block`` queries at a time (a row's queries from its a-th on are a
    row of their own that ends where they end: the mask is causal)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    twin = jax.jit(pa.ragged_paged_attention_jax, static_argnames=("num_kv_heads", "window"))
    if whole:
        return twin(q, k, v, pt, jnp.asarray(q_starts), jnp.asarray(q_lens), jnp.asarray(kv_lens),
                    num_kv_heads=Hkv, window=window)
    out = jnp.zeros_like(q)
    one = lambda x: jnp.asarray([x], jnp.int32)
    for r in np.flatnonzero(q_lens):
        for a in range(0, int(q_lens[r]), block):
            n = min(block, int(q_lens[r]) - a)
            at = int(q_starts[r]) + a
            part = twin(q[at:at + n], k, v, pt[r:r + 1], one(0), one(n),
                        one(int(kv_lens[r]) - int(q_lens[r]) + a + n), num_kv_heads=Hkv, window=window)
            out = out.at[at:at + n].set(part)
    return out


def measure(pa, names=None, window_operand=False, repeats=REPEATS, profile=False) -> dict:
    """Each case's line, for the kernels of the module ``pa``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    chips = json.load(open(os.path.join(HERE, "bench", "chips.json")))
    chip = chips.get(dev.device_kind)
    interpret = dev.platform != "tpu"
    bf = jnp.bfloat16
    lines = {}
    for name, (Hq, Hkv, D, window, R, T, rows, *deep) in _cases().items():
        if names and name not in names:
            continue
        max_pages = deep[0] if deep else MAX_PAGES
        rng = np.random.default_rng(len(name))
        q_lens = np.zeros(R, np.int32)
        kv_lens = np.zeros(R, np.int32)
        q_starts = np.zeros(R, np.int32)
        slots = rng.permutation(R)[:len(rows)]
        off = 0
        # One-query rows are packed first, as the scheduler packs them.
        for slot, (ql, kl) in sorted(zip(slots, rows), key=lambda sr: sr[1][0] > 1):
            q_starts[slot], q_lens[slot], kv_lens[slot] = off, ql, kl
            off += ql
        assert off <= T, (name, off, T)
        P = R * max_pages
        normal = (lambda size: rng.standard_normal(size, dtype=np.float32)) if deep else (
            lambda size: rng.normal(size=size))
        q = jnp.asarray(normal((T, Hq, D)), bf)
        k = jnp.asarray(normal((P, PAGE, Hkv * D)), bf)
        v = jnp.asarray(normal((P, PAGE, Hkv * D)), bf)
        pt = jnp.asarray(rng.permutation(P).reshape(R, max_pages).astype(np.int32))
        descr = (jnp.asarray(q_starts), jnp.asarray(q_lens), jnp.asarray(kv_lens))

        kw = {"window": window}
        if window_operand and any(name.startswith(m) for m in MIXED_KINDS):
            kw = {"window_dyn": jnp.int32(window or pa.GLOBAL_WINDOW)}

        def call(q_, k_, v_, pt_, *descr_):
            return pa.ragged_paged_attention_tpu(q_, k_, v_, pt_, *descr_, Hkv,
                                                 interpret=interpret, **kw)

        ref = _twin(pa, q, k, v, pt, q_starts, q_lens, kv_lens, Hkv, window, whole=not deep)
        lines[name] = _timed(call, q, k, v, ref, rows, Hq, Hkv, D, window, chip, repeats, profile=profile,
                             operands=(pt, *descr))
        try:
            lines[name]["plan"] = pa.ragged_tile_plan(Hq, Hkv, D, PAGE, max_pages, bf)
        except TypeError:  # a tree before ISSUE 44: the plan knew the groups and the page alone
            lines[name]["plan"] = pa.ragged_tile_plan(Hq // Hkv, PAGE)
        print(name, json.dumps(lines[name]), flush=True)

    for name, (Hq, Hkv, D, window, B, contexts) in _decode_cases().items():
        if names and not any(name.startswith(n) for n in names):
            continue
        rng = np.random.default_rng(len(name))
        lengths = np.zeros(B, np.int32)
        lengths[rng.permutation(B)[:len(contexts)]] = contexts
        P = B * MAX_PAGES
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), bf)
        k = jnp.asarray(rng.normal(size=(P, PAGE, Hkv * D)), bf)
        v = jnp.asarray(rng.normal(size=(P, PAGE, Hkv * D)), bf)
        pt = jnp.asarray(rng.permutation(P).reshape(B, MAX_PAGES).astype(np.int32))
        lens = jnp.asarray(lengths)
        rows = [(1, c) for c in contexts]
        live = (lens > 0)[:, None, None]

        kw = {"window": window}
        if window_operand and any(name.startswith(f"decode:{m}") for m in MIXED_KINDS):
            kw = {"window_dyn": jnp.int32(window or pa.GLOBAL_WINDOW)}

        def decode(q_, k_, v_):
            return pa.paged_attention_tpu(q_, k_, v_, pt, lens, Hkv, interpret=interpret, **kw)

        def one_query(q_, k_, v_):  # slot b's query is packed position b
            return pa.ragged_paged_attention_tpu(
                q_, k_, v_, pt, jnp.arange(B, dtype=jnp.int32), (lens > 0).astype(jnp.int32), lens,
                Hkv, interpret=interpret, **kw)

        # An empty slot's row is the decode kernel's own business (undefined in
        # the parent's, zeros since ISSUE 37): the distance is over live rows.
        ref = jax.jit(pa.paged_attention_jax, static_argnames=("num_kv_heads", "window"))(
            q, k, v, pt, lens, num_kv_heads=Hkv, window=window)
        line = {"slots": B, "live": len(contexts), "pages_visible": sum(
            -(-c // PAGE) - max(c - (window or c), 0) // PAGE for c in contexts)}
        for label, fn in (("decode_kernel", decode), ("ragged_one_query", one_query)):
            line[label] = _timed(fn, q, k, v, ref, rows, Hq, Hkv, D, window, chip, repeats, live, profile, DECODE_CALLS)
            empty = fn(q, k, v)[np.flatnonzero(lengths == 0)]
            line[label]["empty_rows_zero"] = bool(jnp.all(empty == 0))
        if hasattr(pa, "decode_walk_plan"):
            line["plan"] = pa.decode_walk_plan(Hq, Hkv, D, PAGE, MAX_PAGES, bf)
        lines[name] = line
        print(name, json.dumps(line), flush=True)
    return lines


def measure_keye(sa, names=None, repeats=REPEATS, profile=False) -> dict:
    """``keye-one-query:<context>/<pages>``: ``sa.sparse_decode`` whole, its read
    forced each way (a tree before ISSUE 45 has the gather alone)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    B, Hq, Hkv, D, Hi, Di, topk = (KEYE[n] for n in ("B", "Hq", "Hkv", "D", "Hi", "Di", "topk"))
    bf = jnp.bfloat16
    lines = {}
    for ctx, pages in KEYE_ONE_QUERY:
        name = f"keye-one-query:{ctx}/{pages}"
        if names and not any(name.startswith(n) for n in names):
            continue
        rng = np.random.default_rng(ctx)
        P = B * pages
        normal = lambda *shape: jnp.asarray(rng.standard_normal(shape, dtype=np.float32), bf)
        q, qi, wi = normal(B, Hq, D), normal(B, Hi, Di), jnp.asarray(rng.standard_normal((B, Hi), dtype=np.float32))
        flat_k, flat_v, ki = normal(P * PAGE, Hkv * D), normal(P * PAGE, Hkv * D), normal(P, Di, PAGE)
        table = jnp.asarray(rng.permutation(P).reshape(B, pages).astype(np.int32))
        lengths = jnp.full((B,), ctx, jnp.int32)
        line, outs = {"rows": B, "context": ctx, "row_tokens": pages * PAGE, "topk": topk}, {}
        for read, under in (("gather", 0), ("walk", 1 << 20)):
            if read == "walk" and not hasattr(sa, "WALK_UNDER"):
                continue
            if hasattr(sa, "WALK_UNDER"):
                sa.WALK_UNDER = under

            def call(q_, qi_, k_, v_, wi_, ki_, table_, lengths_):
                return sa.sparse_decode(q_, qi_, wi_, k_, v_, ki_, table_, lengths_, topk=topk,
                                        num_kv_heads=Hkv, page_size=PAGE)

            def chain(i, carry, *ops):
                # A call's output is the next one's query, and moves its indexer's too: scores, selection and
                # read all follow the carry (what does not is hoisted out of the loop and timed once in 128).
                q_, qi_ = carry
                out = call(q_, qi_, *ops)
                return out, (qi_ + out[:, :Hi, :Di] * 0.05).astype(qi_.dtype)

            operands = (flat_k, flat_v, wi, ki, table, lengths)
            chained = jax.jit(lambda q_, qi_, *ops: jax.lax.fori_loop(
                0, DECODE_CALLS, lambda i, c: chain(i, c, *ops), (q_, qi_)))
            outs[read] = jax.block_until_ready(jax.jit(call)(q, qi, *operands))
            times = _clock(lambda: chained(q, qi, *operands), repeats, DECODE_CALLS)
            line[read] = {"us_per_call": round(statistics.median(times) * 1e6, 1), "us_min": round(min(times) * 1e6, 1)}
            if profile:
                line[read]["device_us_by_op"] = _device_us_by_op(
                    lambda: jax.block_until_ready(chained(q, qi, *operands)), DECODE_CALLS, top=12)
        if len(outs) == 2:
            line["max_abs_diff_walk_vs_gather_bf16"] = float(jnp.max(jnp.abs(
                outs["walk"].astype(jnp.float32) - outs["gather"].astype(jnp.float32))))
            line["gather_over_walk"] = round(line["gather"]["us_per_call"] / line["walk"]["us_per_call"], 3)
        lines[name] = line
        print(name, json.dumps(line), flush=True)
    return lines


if __name__ == "__main__":
    raise SystemExit(main())
