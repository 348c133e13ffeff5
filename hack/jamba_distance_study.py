#!/usr/bin/env python3
"""By hand, on the chip: WHAT of the served precision sets `jamba2-3b.long-context`'s
distance from its reference (ISSUE 64's review: a sound run reads a mean of 0.07-0.09
nats against a limit of 0.12, twice the dense cells').

The engine at the cell's sizes (int8 weights, bfloat16 activations, the float32 state)
takes two prompts through mixed steps of 512 tokens, one beside the other's decode row,
then both decode greedily; the served logprobs are held to
`bench/references/mamba1_mqa.py` (float32, precision "highest", the program's own
weights) as `tests/test_jamba.py` holds the tiny preset. Each VARIANT is a process of
its own that lifts ONE part of the served path to float32 and leaves the rest as it is
served:

- `served`: nothing lifted.
- `small_products`: inside the 26 mixers, c and Delta's bottleneck are NOT rounded to
  bfloat16 before W_x and W_dt, those two products and the mixer's elementwise work run
  in float32; W_in and W_out still take bfloat16 rows.
- `mixers`: everything inside the 26 mixers in float32 (W_in and W_out too); the
  stream, the MLPs and the attention layers as served.
- `float32`: the whole program with float32 activations at the highest matmul
  precision (the int8 weights and the bfloat16 KV cache stay).

    chiprun -- python3 hack/jamba_distance_study.py [--variants ...] [--prompts 1525 2525] [--chunks 8]

Not part of the benchmark; `--rehearse-cpu` walks the tiny preset here.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
VARIANTS = ["served", "small_products", "mixers", "float32"]


def child(variant: str, prompts: list, chunks: int, rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, str(ROOT))
    if variant == "float32":
        jax.config.update("jax_default_matmul_precision", "highest")
    from inference_gateway_tpu.ops import quant, ssm
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from tests import test_jamba
    from tests.test_lfm2 import admit, decode

    mixer, product = ssm.mamba_mixer, ssm.qmatmul
    if variant in ("small_products", "mixers"):
        def lifted(h, *rest, **kw):
            with jax.default_matmul_precision("highest"):
                out, y, store = mixer(h.astype(jnp.float32), *rest, **kw)
            return out.astype(h.dtype), y, store

        ssm.mamba_mixer = lifted
        if variant == "small_products":   # the two large products as served: bfloat16 rows in, bfloat16 out
            ssm.qmatmul = lambda x, w: (product(x.astype(jnp.bfloat16), w).astype(jnp.float32)
                                        if isinstance(w, quant.QTensor) else product(x, w))
    config = json.loads((ROOT / "bench" / "configs" / "jamba2-3b-int8.json").read_text())
    if rehearse:
        model, sizes = config["rehearsal"]["model"], dict(model="jamba-test-tiny", page_size=8, max_slots=4, max_seq_len=256,
                                                          mixed_step_tokens=40, decode_chunk=4)
    else:
        dep = config["deployment_sizes"]
        model, sizes = config, dict(model=config["preset"], page_size=dep["page_size"], max_slots=4, max_seq_len=dep["max_seq_len"],
                                    mixed_step_tokens=dep["mixed_step_tokens"], decode_chunk=dep["decode_chunk"])
    eng = Engine(EngineConfig(attention="paged", quantize="int8", dtype="float32" if variant == "float32" else "bfloat16",
                              mixed_step=True, use_mesh=False, **sizes))
    chunk = sizes["mixed_step_tokens"] - sizes["max_slots"]
    rng = np.random.default_rng(64)
    ids = [[int(t) for t in rng.integers(32, 127, size=n)] for n in prompts]
    tok0, lp0, _ = admit(eng, 0, ids[0], chunks=(chunk,))
    row0 = [tok0, len(ids[0]), [tok0], [lp0]]
    tok1, lp1, _ = admit(eng, 1, ids[1], beside={0: row0}, chunks=(chunk,))
    toks, lps, _ = decode(eng, {0: (row0[0], row0[1]), 1: (tok1, len(ids[1]))}, chunks)
    rows = [(ids[0] + row0[2] + toks[0], len(ids[0]), row0[3] + lps[0]), (ids[1] + [tok1] + toks[1], len(ids[1]), [lp1] + lps[1])]
    diffs = [test_jamba.gaps(eng, seq, start, served, model) for seq, start, served in rows]
    both = np.concatenate(diffs)
    return {"variant": variant, "prompts": prompts, "compared": int(both.size), "max_abs_diff": float(both.max()),
            "mean_abs_diff": float(both.mean()), "by_row": [[float(d.max()), float(d.mean())] for d in diffs],
            "device": jax.devices()[0].platform}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", nargs="+", default=VARIANTS, choices=VARIANTS)
    ap.add_argument("--prompts", nargs=2, type=int, default=[1525, 2525])
    ap.add_argument("--chunks", type=int, default=8, help="decode chunks of 8 tokens a row")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--child")
    args = ap.parse_args()
    if args.rehearse_cpu:
        args.prompts = [min(p, 60 + 40 * i) for i, p in enumerate(args.prompts)]
    if args.child:
        print(json.dumps(child(args.child, args.prompts, args.chunks, args.rehearse_cpu)), flush=True)
        return 0
    rc = 0
    for variant in args.variants:   # a process each: the chip belongs to one at a time, and an engine outlives its `del`
        cmd = [sys.executable, __file__, "--child", variant, "--prompts", *map(str, args.prompts), "--chunks", str(args.chunks)]
        out = subprocess.run(cmd + (["--rehearse-cpu"] if args.rehearse_cpu else []), capture_output=True, text=True, cwd=ROOT)
        lines = out.stdout.strip().splitlines()
        print(lines[-1] if out.returncode == 0 and lines else json.dumps({"variant": variant, "rc": out.returncode,
                                                                          "error": out.stderr[-1500:]}), flush=True)
        rc = rc or out.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
