"""The main path's Pallas kernels through the chip's own compiler, no chip.

Interpret mode hides what the TPU compiler refuses (VMEM limits, slices off
the (8,128)/(16,128) tiling), so the decode, ragged and flash-prefill kernels
are compiled here — ``interpret=False``, Mistral-7B widths, the shapes
``chip_smoke.py`` serves — for a *described* v5e:2x2, plus the tp = 4
``shard_map`` launch on a mesh of the described devices. Nothing runs: a pass
says the compiler accepts the kernel, never that it computes the right thing
(``chip_smoke.py``'s kernels phase says that, on the chip).

The only file of its kind. The topology is described inside a module-scoped
fixture, never at import: one process at a time may load the TPU's library,
and every xdist worker imports every test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from inference_gateway_tpu.ops.flash_attention import flash_prefill_attention
from inference_gateway_tpu.ops.paged_attention import (
    paged_attention_sharded,
    paged_attention_tpu,
    ragged_paged_attention_sharded,
    ragged_paged_attention_tpu,
)

# mistral-7b (models/llama.py PRESETS) under chip_smoke.py's sidecar flags.
HQ, HKV, D, PAGE, WINDOW = 32, 8, 128, 128, 4096
SLOTS, SEQ, LAYERS, BUCKET = 24, 2048, 32, 512
MAX_PAGES = SEQ // PAGE
POOL_PAGES = LAYERS * SLOTS * MAX_PAGES  # the engine's layer-stacked pool
MIXED_BUDGET = BUCKET + SLOTS  # Engine.mixed_budget at the CLI's default buckets
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache but
    cannot be read back without a chip (the next one warns and recompiles)."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_to_a_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _pool(sds, pages=POOL_PAGES):
    return sds((pages, PAGE, HKV * D), BF16)


def test_decode_kernel_compiles_for_v5e(one_chip):
    _compiles_to_a_kernel(
        lambda q, k, v, table, lengths: paged_attention_tpu(
            q, k, v, table, lengths, HKV, interpret=False, window=WINDOW),
        one_chip((SLOTS, HQ, D), BF16), _pool(one_chip), _pool(one_chip),
        one_chip((SLOTS, MAX_PAGES), I32), one_chip((SLOTS,), I32))


def test_ragged_kernel_compiles_for_v5e(one_chip):
    row = one_chip((SLOTS,), I32)
    _compiles_to_a_kernel(
        lambda q, k, v, table, starts, lens, kv: ragged_paged_attention_tpu(
            q, k, v, table, starts, lens, kv, HKV, interpret=False, window=WINDOW),
        one_chip((MIXED_BUDGET, HQ, D), BF16), _pool(one_chip), _pool(one_chip),
        one_chip((SLOTS, MAX_PAGES), I32), row, row, row)


def test_flash_prefill_kernel_compiles_for_v5e(one_chip):
    batch = 8  # the CLI's --max-prefill-batch
    kv = one_chip((batch, BUCKET, HKV, D), BF16)
    _compiles_to_a_kernel(
        lambda q, k, v, lengths: flash_prefill_attention(
            q, k, v, lengths, interpret=False, window=WINDOW),
        one_chip((batch, BUCKET, HQ, D), BF16), kv, kv, one_chip((batch,), I32))


def test_tp4_shard_map_launch_compiles_for_v5e(topo):
    """default_mesh_shape(4) on the four-chip host: tp = 4 tiles Mistral's 8 KV
    heads; each shard launches the kernels on its heads, no collectives."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4), ("dp", "sp", "tp"))

    def on(spec):
        return lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    heads, pages, rep = on(P(None, "tp", None)), on(P(None, None, "tp")), on(P())
    table, row = rep((SLOTS, MAX_PAGES), I32), rep((SLOTS,), I32)
    _compiles_to_a_kernel(
        lambda q, k, v, table, lengths: paged_attention_sharded(
            q, k, v, table, lengths, HKV, mesh, window=WINDOW, interpret=False),
        heads((SLOTS, HQ, D), BF16), _pool(pages), _pool(pages), table, row)
    _compiles_to_a_kernel(
        lambda q, k, v, table, starts, lens, kv: ragged_paged_attention_sharded(
            q, k, v, table, starts, lens, kv, HKV, mesh, window=WINDOW, interpret=False),
        heads((MIXED_BUDGET, HQ, D), BF16), _pool(pages), _pool(pages), table, row, row, row)
