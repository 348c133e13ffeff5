"""The latent attention's reads alone on the chip (ISSUE 47), at the published
sizes of ``xing4.0-stage10`` (32 heads, rows of 576 in 640 lanes, pages of 128):

    chiprun -- python3 hack/mla_attention_bench.py [--context 12288] [--chunk 512] [--rehearse]
    chiprun -- python3 hack/mla_attention_bench.py --absorb [--rehearse]

- a mixed step's CHUNK (``--chunk`` queries whose last sees ``--context`` keys),
  ABSORBED through ``mla_chunk_attn_kernel`` at several pages a visit, against
  the same chunk with k_nope and v MATERIALISED from the pages it reads: the
  row's pages gathered, W_UKV applied to every cached token, heads of 192 / 128
  padded to 256 and laid out as pages of 32 key heads for the repo's own chunk
  kernel (``ragged_paged_attention_tpu``; ``flash_prefill_attention`` keeps a
  head's whole key panel in VMEM and runs out at 12k x 256). Both end in the heads' values
  (N, heads, 128), compared here; ISSUE 47 asks which one a chunk's rows take.
- a mixed step's 31 one-query rows at 4k-12k of context through
  ``mla_decode_attn_kernel`` at several pages a visit, with the bytes of latent
  they must read over the time (the kernel's share of the HBM roofline).

- ``--absorb`` (ISSUE 59), alone: both products of the absorbed form from an
  int8 W_UKV, a block after a block over a stack of four layers' matrices as a
  step's scan reads them, at openPangu's decode round (128 query rows, 128
  heads) and Xing's mixed step (544 rows, 32 heads): the parent's form kept
  HERE (W_UKV dequantised whole in bf16, cut into the heads' halves, two
  einsums) against the program's (``mixtral._latent_absorb`` /
  ``_latent_out``: the int8 converted inside each product, the scales on the
  query and on the float32 result), us a block beside the least time of the
  block's bytes (W_UKV once as int8, the queries in, the absorbed queries out,
  the weighted latents in, the values out), and how far the two forms lie apart.

Times are wall clock around ``block_until_ready`` over ``--repeats`` calls of a
jitted function, the pool and the matrices ARGUMENTS. ``--rehearse`` walks it on
the CPU at a toy size (interpreted kernels; never a number).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def absorb_bench(rehearse: bool, repeats: int) -> dict:
    """``--absorb``: {case: {parent_us_a_block, stored_us_a_block, least_us_a_block, ...}}."""
    import jax
    import jax.numpy as jnp

    from inference_gateway_tpu.models import mixtral
    from inference_gateway_tpu.ops.quant import QTensor

    cases = ({"toy (12 rows)": ("xing4-test-tiny", 12)} if rehearse else
             {"openpangu decode round (128 rows, 128 heads)": ("openpangu-ultra-moe-718b-stage7", 128),
              "xing4 mixed step (544 rows, 32 heads)": ("xing4.0-stage10", 544)})
    layers, dtype, result = 4, jnp.bfloat16, {}
    for case, (preset, rows) in cases.items():
        cfg = mixtral.PRESETS[preset]
        H, Dn, Dv, rank = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
        assert Dn == Dv   # a block's values are the next block's queries here, so nothing is hoisted out of the scan
        key = jax.random.split(jax.random.PRNGKey(0), 3)
        unit = jnp.tile(jnp.concatenate([jnp.full(Dn, Dn ** -0.5 / 73), jnp.full(Dv, rank ** -0.5 / 73)]), H)   # a block keeps the rows' size
        stack = QTensor(jax.random.randint(key[0], (layers, rank, H * (Dn + Dv)), -127, 128, jnp.int8),
                        jax.random.uniform(key[1], (layers, 1, H * (Dn + Dv)), jnp.float32, 0.5, 1.5) * unit)
        q_nope = jax.random.normal(key[2], (rows, H, Dn), jnp.float32).astype(dtype)

        def parent(q, w):   # the parent's form, kept as the definition
            w = (w.q.astype(dtype) * w.scale.astype(dtype)).reshape(rank, H, Dn + Dv)
            q_lat = jnp.einsum("nhd,chd->nhc", q, w[..., :Dn], preferred_element_type=jnp.float32).astype(dtype)
            return q_lat, lambda attn: jnp.einsum("nhc,chd->nhd", attn, w[..., Dn:], preferred_element_type=jnp.float32).astype(dtype)

        def stored(q, w):
            q_lat, w_uv = mixtral._latent_absorb(q, w, cfg)
            return q_lat, lambda attn: mixtral._latent_out(attn, w_uv, dtype).reshape(rows, H, Dv)

        def blocks(form):
            def block(q, w):   # the read between the products stands as a barrier: the absorbed queries are written
                q_lat, up = form(q, w)
                return up(jax.lax.optimization_barrier(q_lat)), None
            return jax.jit(lambda q, stack: jax.lax.scan(block, q, stack)[0])

        def timed(fn):
            out = jax.block_until_ready(fn(q_nope, stack))
            t0 = time.perf_counter()
            for _ in range(repeats):
                last = fn(q_nope, stack)
            jax.block_until_ready(last)
            return out.astype(jnp.float32), (time.perf_counter() - t0) / repeats / layers * 1e6

        (want, parent_us), (got, stored_us) = timed(blocks(parent)), timed(blocks(stored))
        moved = rank * H * (Dn + Dv) + 2 * rows * H * (Dn + 2 * rank + Dv)
        result[case] = {"parent_us_a_block": round(parent_us, 1), "stored_us_a_block": round(stored_us, 1),
                        "block_bytes": moved, "wukv_bytes": rank * H * (Dn + Dv),
                        "forms_max_abs_diff": float(jnp.max(jnp.abs(got - want))), "output_rms": float(jnp.sqrt(jnp.mean(want ** 2)))}
        if not rehearse:
            result[case]["least_us_a_block"] = round(moved / 819e9 * 1e6, 1)
    return result


def main() -> int:
    cli = argparse.ArgumentParser()
    cli.add_argument("--absorb", action="store_true")
    cli.add_argument("--context", type=int, default=12288)
    cli.add_argument("--chunk", type=int, default=512)
    cli.add_argument("--repeats", type=int, default=10)
    cli.add_argument("--rehearse", action="store_true")
    args = cli.parse_args()

    from inference_gateway_tpu.utils.platform import force_platform

    force_platform("cpu" if args.rehearse else "tpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    if args.absorb:
        print(json.dumps({"device": str(jax.devices()[0].device_kind), "absorb": absorb_bench(args.rehearse, 1 if args.rehearse else args.repeats)}))
        return 0

    from inference_gateway_tpu.ops import mla_attention as mla
    from inference_gateway_tpu.ops.paged_attention import ragged_paged_attention_tpu

    H, rank, rope, nope, vdim, page, lanes = 32, 512, 64, 128, 128, 128, 640
    context, chunk, rows, repeats = args.context, args.chunk, 31, args.repeats
    if args.rehearse:
        H, rank, rope, nope, vdim, page, lanes = 4, 32, 8, 16, 16, 8, 128
        context, chunk, rows, repeats = 96, 24, 3, 1
    interpret = args.rehearse
    scale = (nope + rope) ** -0.5 * 2.0
    max_pages = -(-context // page)
    pool_pages = (rows + 1) * max_pages
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    dtype = jnp.bfloat16
    pool = jax.random.normal(key[0], (pool_pages, page, lanes), jnp.float32)
    pool = pool.at[:, :, rank + rope:].set(0).astype(dtype)
    w_ukv = (jax.random.normal(key[1], (rank, H, nope + vdim), jnp.float32) * rank ** -0.5).astype(dtype)
    q_nope = jax.random.normal(key[2], (chunk + rows, H, nope), jnp.float32).astype(dtype)
    q_rope = jax.random.normal(key[3], (chunk + rows, H, rope), jnp.float32).astype(dtype)
    table = jnp.asarray(np.random.default_rng(0).permutation(pool_pages).reshape(rows + 1, max_pages), jnp.int32)
    lengths = jnp.asarray(np.random.default_rng(1).integers(context // 3, context, size=rows), jnp.int32)

    def absorb(q_nope, q_rope):
        lat = jnp.einsum("nhd,chd->nhc", q_nope, w_ukv[..., :nope], preferred_element_type=jnp.float32)
        return jnp.concatenate([lat.astype(dtype), q_rope], axis=-1)

    def up(attn):
        return jnp.einsum("nhc,chd->nhd", attn, w_ukv[..., nope:], preferred_element_type=jnp.float32).astype(dtype)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(*a)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / repeats * 1e3

    result = {"device": str(jax.devices()[0].device_kind), "context": context, "chunk": chunk, "heads": H}
    # The chunk, absorbed: one row of ``chunk`` queries whose last sees ``context`` keys.
    one = lambda v: jnp.asarray([v], jnp.int32)
    absorbed = {}
    for visit in (2, 4, 8):
        fn = jax.jit(lambda pool, qn, qr, visit=visit: up(mla.mla_ragged_attention_tpu(
            absorb(qn, qr), pool, table[:1], one(0), one(chunk), one(context), value_dim=rank, scale=scale,
            interpret=interpret, pages_per_visit=visit)))
        out_a, ms = timed(fn, pool, q_nope[:chunk], q_rope[:chunk])
        absorbed[f"pages_per_visit_{visit}"] = round(ms, 3)
    result["chunk_absorbed_ms"] = absorbed

    # The chunk, materialised: every cached token's k_nope and v up from the pages the row holds.
    def materialised(pool, qn, qr):
        lat = pool[table[0]].reshape(max_pages * page, lanes)
        kv = jnp.einsum("sc,chd->shd", lat[:, :rank], w_ukv, preferred_element_type=jnp.float32).astype(dtype)
        S, pad = kv.shape[0], 2 * nope - nope - rope
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(lat[:, None, rank:rank + rope], (S, H, rope)),
                             jnp.zeros((S, H, pad), dtype)], axis=-1)
        v = jnp.concatenate([kv[..., nope:], jnp.zeros((S, H, 2 * nope - vdim), dtype)], axis=-1)
        q = jnp.concatenate([qn, qr, jnp.zeros((chunk, H, pad), dtype)], axis=-1) * jnp.asarray(
            scale * (2 * nope) ** 0.5, dtype)   # the kernel scales by its own head size
        paged = lambda a: a.reshape(max_pages, page, H * 2 * nope)
        out = ragged_paged_attention_tpu(q, paged(k), paged(v), jnp.arange(max_pages, dtype=jnp.int32)[None],
                                         one(0), one(chunk), one(context), H)
        return out[:, :, :vdim]

    if not args.rehearse:
        try:
            out_m, ms = timed(jax.jit(materialised), pool, q_nope[:chunk], q_rope[:chunk])
            result["chunk_materialised_ms"] = round(ms, 3)
            result["chunk_paths_max_abs_diff"] = float(jnp.max(jnp.abs(out_a.astype(jnp.float32) - out_m.astype(jnp.float32))))
            result["chunk_output_rms"] = float(jnp.sqrt(jnp.mean(jnp.square(out_a.astype(jnp.float32)))))
        except Exception as e:  # the rest of the table is still worth the call
            result["chunk_materialised_error"] = str(e)[:400]

    # The one-query rows.
    decode = {}
    latent_bytes = int(jnp.sum(-(-lengths // page))) * page * (rank + rope) * 2
    for visit in (4, 8, 11):
        fn = jax.jit(lambda pool, qn, qr, visit=visit: up(mla.mla_paged_attention_tpu(
            absorb(qn, qr), pool, table[1:], lengths, value_dim=rank, scale=scale, interpret=interpret,
            pages_per_visit=visit)))
        _, ms = timed(fn, pool, q_nope[chunk:], q_rope[chunk:])
        decode[f"pages_per_visit_{visit}"] = round(ms, 3)
    result["decode_rows_ms"] = decode
    result["decode_rows_latent_bytes"] = latent_bytes
    if not args.rehearse:
        result["decode_rows_hbm_share_at_best"] = round(latent_bytes / 819e9 * 1e3 / min(decode.values()), 3)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
