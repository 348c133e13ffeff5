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


@pytest.fixture
def as_on_tpu(monkeypatch):
    """``utils.platform.on_tpu`` says yes, as on the chip: the expert layer
    then makes its own choice of kernel, compiled and not interpreted."""
    monkeypatch.setattr("inference_gateway_tpu.utils.platform.device_platform", lambda: "tpu")


@pytest.mark.parametrize("tokens", [48, 560], ids=["decode_48_rows", "mixed_step_560_tokens"])
def test_dropless_expert_layer_compiles_for_v5e_with_no_copy_of_the_stack(one_chip, as_on_tpu, tokens):
    """SmallThinker's expert layer at its published widths (64 experts of
    2560 x 768, top-6, int8 stacks of the 16-layer stage) through the chip's
    compiler: both grouped matmuls are kernels, the stacks reach them whole
    (neither a bf16 copy of a layer's experts, which ``jax.lax.ragged_dot`` on
    the dequantised layer makes, nor an int8 slice of a leaf), and the
    temporaries are the rows', a few MB."""
    from inference_gateway_tpu.ops.moe import moe_dropless
    from inference_gateway_tpu.ops.quant import QTensor

    L, E, H, I, K = 16, 64, 2560, 768, 6

    def stack(k, n):
        return QTensor(one_chip((L, E, k, n), jnp.int8), one_chip((L, E, 1, n), jnp.float32))

    compiled = jax.jit(lambda x, logits, wg, wu, wd, li: moe_dropless(
        x, logits, K, wg, wu, wd, li, act="relu", valid=jnp.arange(tokens) < tokens - 3)).lower(
        one_chip((tokens, H), BF16), one_chip((tokens, E), jnp.float32), stack(H, I), stack(H, I), stack(I, H),
        one_chip((), I32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "moe_gate_up_gmm" in text and "moe_down_gmm" in text
    assert f"bf16[{E},{H},{I}]" not in text and f"s8[1,{E},{H},{I}]" not in text and f"s8[{E},{H},{I}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# (sizes L, E, H, I, top-k; tokens): SmallThinker's stage at a decode step and a
# mixed step, Mixtral-8x7B at a mixed step of chip_smoke.py's budget.
SMALLTHINKER, MIXTRAL = (16, 64, 2560, 768, 6), (32, 8, 4096, 14336, 2)


@pytest.mark.parametrize("sizes,tokens,ep,tp", [
    (SMALLTHINKER, 48, 4, 1), (SMALLTHINKER, 560, 4, 1), (SMALLTHINKER, 560, 2, 2), (SMALLTHINKER, 48, 1, 4),
    (MIXTRAL, MIXED_BUDGET, 4, 1), (MIXTRAL, MIXED_BUDGET, 1, 4),
], ids=["smallthinker_decode_ep4", "smallthinker_mixed_ep4", "smallthinker_mixed_ep2_tp2",
        "smallthinker_decode_tp4", "mixtral_mixed_ep4", "mixtral_mixed_tp4"])
def test_dropless_expert_layer_compiles_on_the_four_chip_mesh(topo, as_on_tpu, sizes, tokens, ep, tp):
    """The layer as the engine calls it (no override: ``on_tpu`` alone picks the
    kernel) on a v5e:2x2 with the int8 stacks laid out by ``param_specs``: a
    Mosaic kernel cannot be partitioned automatically, so the layer launches it
    under ``shard_map`` on each shard's own experts and columns, and the only
    collective is the one all-reduce of the (tokens, hidden) result; no shard
    gathers an expert it does not hold, and no copy of a stack is made.
    SmallThinker's 768 columns over tp = 4 are 192 a shard, not whole lanes: the
    kernel would have the compiler re-lay the whole stack out per call (1.3 GB),
    so that shard's gate and up matrices take the twin, one layer's copy."""
    from inference_gateway_tpu.models import mixtral
    from inference_gateway_tpu.ops.moe import moe_dropless
    from inference_gateway_tpu.ops.quant import QTensor
    from inference_gateway_tpu.parallel.sharding import quantized_specs

    L, E, H, I, K = sizes
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, ep, tp), ("dp", "sp", "ep", "tp"))
    specs = quantized_specs(mixtral.param_specs(mixtral.PRESETS["mixtral-test-tiny"]))["layers"]

    def on(spec):
        return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    def stack(name, k, n):
        return QTensor(on(specs[name].q)((L, E, k, n), jnp.int8), on(specs[name].scale)((L, E, 1, n), jnp.float32))

    rep = on(P())
    compiled = jax.jit(lambda x, logits, wg, wu, wd, li: moe_dropless(
        x, logits, K, wg, wu, wd, li, act="relu", valid=jnp.arange(tokens) < tokens - 3, mesh=mesh)).lower(
        rep((tokens, H), BF16), rep((tokens, E), jnp.float32), stack("wg", H, I), stack("wu", H, I),
        stack("wd", I, H), rep((), I32)).compile()
    text = compiled.as_text()
    assert "moe_down_gmm" in text and ("moe_gate_up_gmm" in text) == ((I // tp) % 128 == 0)
    assert text.count("all-reduce(") == 1 and "all-gather(" not in text and "all-to-all(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# phi-4-mini-flash (models/phi4flash.py PRESETS) under its cell's sidecar flags:
# differential attention in the zero-padded form runs both kernels at 40 query
# heads over 10 KV heads of 128 (20 x 64 as they lie in the cache row), pages of
# 128, 64 slots, a pool of 9 caching layers; windowed (512) and full.
PHI = dict(hq=40, hkv=10, d=128, slots=64, layers=9, budget=512 + 64, di=5120, n=16, k=4)


def _phi_pool(sds):
    return sds((PHI["layers"] * PHI["slots"] * MAX_PAGES, PAGE, PHI["hkv"] * PHI["d"]), BF16)


@pytest.mark.parametrize("window", [512, None], ids=["window_512", "full"])
def test_decode_kernel_compiles_at_ten_kv_heads_for_v5e(one_chip, window):
    _compiles_to_a_kernel(
        lambda q, k, v, table, lengths: paged_attention_tpu(
            q, k, v, table, lengths, PHI["hkv"], interpret=False, window=window),
        one_chip((PHI["slots"], PHI["hq"], PHI["d"]), BF16), _phi_pool(one_chip), _phi_pool(one_chip),
        one_chip((PHI["slots"], MAX_PAGES), I32), one_chip((PHI["slots"],), I32))


@pytest.mark.parametrize("window", [512, None], ids=["window_512", "full"])
def test_ragged_kernel_compiles_at_ten_kv_heads_for_v5e(one_chip, window):
    row = one_chip((PHI["slots"],), I32)
    _compiles_to_a_kernel(
        lambda q, k, v, table, starts, lens, kv: ragged_paged_attention_tpu(
            q, k, v, table, starts, lens, kv, PHI["hkv"], interpret=False, window=window),
        one_chip((PHI["budget"], PHI["hq"], PHI["d"]), BF16), _phi_pool(one_chip), _phi_pool(one_chip),
        one_chip((PHI["slots"], MAX_PAGES), I32), row, row, row)


@pytest.mark.parametrize("form", ["decode", "ragged"])
def test_state_operations_compile_for_v5e_at_the_published_widths(one_chip, form):
    """The convolution and the selective scan of one Mamba layer (ops/ssm.py) at
    64 slots x 5,120 x 16 float32: the batched decode update, and the mixed
    step's token loop over 576 packed tokens. Plain XLA: a pass says the chip's
    compiler takes them and holds the store once (no second copy of it)."""
    from inference_gateway_tpu.ops import ssm

    S, T, Di, N, K = PHI["slots"], PHI["budget"], PHI["di"], PHI["n"], PHI["k"]
    F32 = jnp.float32
    states, tails = one_chip((S, Di, N), F32), one_chip((S, K - 1, Di), F32)
    w, b, A, D = one_chip((K, Di), F32), one_chip((Di,), F32), one_chip((Di, N), F32), one_chip((Di,), F32)
    row = one_chip((S,), I32)
    if form == "decode":
        def step(u, tails, w, b, states, delta, B, C, A, D, valid):
            c, tails = ssm.conv_decode(u, tails, w, b, valid)
            return ssm.scan_decode(states, delta, c, B, C, A, D, valid), tails

        args = (one_chip((S, Di), BF16), tails, w, b, states, one_chip((S, Di), F32), one_chip((S, N), F32),
                one_chip((S, N), F32), A, D, one_chip((S,), jnp.bool_))
    else:
        def step(u, tails, w, b, states, delta, B, C, A, D, starts, lens, kv, positions):
            seg = ssm.token_segments(starts, lens, T)
            c, tails = ssm.conv_ragged(u, tails, w, b, seg, (kv - lens) == 0, starts, lens)
            return ssm.scan_ragged(states, delta, c, B, C, A, D, seg, positions, jnp.sum(lens)), tails

        args = (one_chip((T, Di), BF16), tails, w, b, states, one_chip((T, Di), F32), one_chip((T, N), F32),
                one_chip((T, N), F32), A, D, row, row, row, one_chip((T,), I32))
    compiled = jax.jit(step, donate_argnums=(1, 4)).lower(*args).compile()
    store = S * Di * N * 4
    assert compiled.memory_analysis().temp_size_in_bytes < store + (64 << 20)
