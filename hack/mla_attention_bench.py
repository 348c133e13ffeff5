"""The latent attention's reads alone on the chip (ISSUE 47), at the published
sizes of ``xing4.0-stage10`` (32 heads, rows of 576 in 640 lanes, pages of 128):

    chiprun -- python3 hack/mla_attention_bench.py [--context 12288] [--chunk 512] [--rehearse]

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

Times are wall clock around ``block_until_ready`` over ``--repeats`` calls of a
jitted function, the pool an ARGUMENT. ``--rehearse`` walks it on the CPU at a
toy size (interpreted kernels; never a number).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    cli = argparse.ArgumentParser()
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
