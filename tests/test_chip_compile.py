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

import re

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


# A mixed step's attention as each benchmark cell hands it over (ISSUE 34: the wide
# tile is 128 queries x G rows a KV head, so G and the folded axis decide what the
# compiler is asked for; ISSUE 44: the launch holds TWO kernels, the ragged one at one
# static width and the decode kernel for the one-query rows of the packed prefix):
# name -> (Hq, Hkv, D, window, slots, caching layers, a row's pages, the most equations
# the ragged kernel's traced body may hold, and the two bodies together).
# ``"operand"``: the window is the kernels' scalar operand, as a model that mixes
# windowed and global layers hands it. The last two numbers are the start-up guard:
# every start traces and lowers the kernels again (the trace once, the conversion to
# MLIR once a variant: the unmasked step program and the masked one), at ~0.35 ms an
# equation in the sandbox and three to five times that on the chip's host, and the
# persistent cache saves none of it. The first tree's 8-query body held 411 / 245 /
# 263 + 245 / 485 + 467; PR 33's three bodies 1,194 / 711 / 954 + 711 / 1,314 + 1,071,
# and cost the mixture's cell 4.4 s of warm ``setup_s`` (PERF.md §6, PR 34); PR 34's two
# widths 434 / 340 / 379 / 463 (Mistral, Qwen2.5, SmallThinker, Phi-4). This tree's
# ragged body reads 323 / 275 / 296 / 338 (one width, a visit of several pages from a
# multiple of them, the mask a pass of its own under ``pl.when``) and the decode kernel's
# beside it 131 / 120 / 132 / 132: together 454 / 395 / 428 / 470, which is 20 / 55 / 49 /
# 7 over PR 34's (a decode-kernel instance is more than a width was; PERF.md §6, PR 44,
# has what that costs a start). The ceilings leave a tenth.
RAGGED_LAYOUTS = {
    "mistral-7b": (HQ, HKV, D, WINDOW, SLOTS, LAYERS, MAX_PAGES, 355, 499),
    "qwen2.5-7b": (28, 4, D, None, 48, 28, MAX_PAGES, 302, 434),
    "smallthinker-21b-a3b": (28, 4, D, "operand", 48, 16, MAX_PAGES, 325, 470),
    "phi-4-mini-flash": (40, 10, D, "operand", 64, 9, MAX_PAGES, 372, 517),
    # 100-page rows, two scanned groups a step (ISSUE 41); heads of 64 (ISSUE 43)
    "trinity-mini": (32, 4, D, "operand", 32, 8, 100, 325, 470),
    "lfm2-8b-a1b": (32, 8, 64, None, 64, 6, MAX_PAGES, 442, 660),
}


def _ragged_launch(sds, hq, hkv, d, window, slots, cache_layers, max_pages):
    """(fn, shapes) of one launch at a cell's layout; ``sds(shape, dtype)``."""
    row = sds((slots,), I32)
    pool = sds((cache_layers * slots * max_pages, PAGE, hkv * d), BF16)  # the engine's, layer-stacked
    shapes = [sds((BUCKET + slots, hq, d), BF16), pool, pool, sds((slots, max_pages), I32), row, row, row]
    if window == "operand":
        return (lambda q, k, v, table, starts, lens, kv, w: ragged_paged_attention_tpu(
            q, k, v, table, starts, lens, kv, hkv, interpret=False, window_dyn=w)), shapes + [sds((), I32)]
    return (lambda q, k, v, table, starts, lens, kv: ragged_paged_attention_tpu(
        q, k, v, table, starts, lens, kv, hkv, interpret=False, window=window)), shapes


def _inner_jaxprs(eqn):
    """The jaxprs an equation holds: a loop's body, a branch, a kernel's."""
    for value in eqn.params.values():
        for inner in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield inner


def _kernel_bodies(jaxpr, name="_ragged_paged_attn_kernel"):
    """The body of every ``pallas_call`` of that kernel under ``jaxpr``, loops
    and branches included (a name that begins the kernel function's: the
    decode kernel's ``_paged_attn_kernel`` is not the ragged one's tail)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "pallas_call":
            found += [body for inner in _inner_jaxprs(eqn) for body in _kernel_bodies(inner, name)]
        elif eqn.params["jaxpr"].debug_info.func_src_info.startswith(name):
            found.append(eqn.params["jaxpr"])
    return found


def _equations(jaxpr) -> int:
    return len(jaxpr.eqns) + sum(_equations(inner) for eqn in jaxpr.eqns for inner in _inner_jaxprs(eqn))


@pytest.mark.parametrize("layout", list(RAGGED_LAYOUTS))
def test_ragged_kernel_compiles_for_v5e(one_chip, layout):
    """Both kernels of the launch through the chip's compiler at every family's
    layout, and their outputs joined with no scatter."""
    fn, shapes = _ragged_launch(one_chip, *RAGGED_LAYOUTS[layout][:-2])
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "scatter" not in text


@pytest.mark.parametrize("layout", list(RAGGED_LAYOUTS))
def test_ragged_kernel_stays_inside_its_start_up_budget(layout):
    """No clock: the count of traced equations, which is what a start pays for."""
    *launch, ceiling, both = RAGGED_LAYOUTS[layout]
    fn, shapes = _ragged_launch(jax.ShapeDtypeStruct, *launch)
    jaxpr = jax.make_jaxpr(fn)(*shapes).jaxpr
    (body,) = _kernel_bodies(jaxpr)
    (rows,) = _kernel_bodies(jaxpr, "_paged_attn_kernel")  # the one-query rows' decode kernel
    assert _equations(body) <= ceiling
    assert _equations(body) + _equations(rows) <= both


# The decode kernel as each benchmark cell's decode program hands it over (ISSUE
# 37: the pages a visit follow from the page's bytes, so Hkv decides what the
# compiler is asked for), the columns as RAGGED_LAYOUTS'. The parent's body (one
# page a visit, float32 operands, two unrolled loops over Hkv) held 210 / 129 /
# 186 + 129 / 222 + 165 equations, the mixture and the hybrid two instances under a
# ``lax.cond``; this tree reads 131 / 120 / 132 / 132, one instance each: the
# ceilings leave a tenth and none is over the parent's.
DECODE_LAYOUTS = {
    "mistral-7b": (HQ, HKV, WINDOW, SLOTS, LAYERS, 148),
    "qwen2.5-7b": (28, 4, None, 48, 28, 129),
    "smallthinker-21b-a3b": (28, 4, "operand", 48, 16, 149),
    "phi-4-mini-flash": (40, 10, "operand", 64, 9, 149),
}


def _decode_launch(sds, hq, hkv, window, slots, cache_layers):
    pool = sds((cache_layers * slots * MAX_PAGES, PAGE, hkv * D), BF16)
    shapes = [sds((slots, hq, D), BF16), pool, pool, sds((slots, MAX_PAGES), I32), sds((slots,), I32)]
    if window == "operand":
        return (lambda q, k, v, table, lengths, w: paged_attention_tpu(
            q, k, v, table, lengths, hkv, interpret=False, window_dyn=w)), shapes + [sds((), I32)]
    return (lambda q, k, v, table, lengths: paged_attention_tpu(
        q, k, v, table, lengths, hkv, interpret=False, window=window)), shapes


@pytest.mark.parametrize("layout", list(DECODE_LAYOUTS))
def test_decode_kernel_compiles_at_each_cells_layout_for_v5e(one_chip, layout):
    fn, shapes = _decode_launch(one_chip, *DECODE_LAYOUTS[layout][:-1])
    _compiles_to_a_kernel(fn, *shapes)


@pytest.mark.parametrize("layout", list(DECODE_LAYOUTS))
def test_decode_kernel_stays_inside_its_start_up_budget(layout):
    *launch, ceiling = DECODE_LAYOUTS[layout]
    fn, shapes = _decode_launch(jax.ShapeDtypeStruct, *launch)
    (body,) = _kernel_bodies(jax.make_jaxpr(fn)(*shapes).jaxpr, "_paged_attn_kernel")
    assert _equations(body) <= ceiling


@pytest.mark.parametrize("module,preset", [("mixtral", "smallthinker-test-tiny"),
                                           ("phi4flash", "phi4flash-test-tiny")])
def test_a_mixed_step_with_two_kinds_of_layer_holds_one_ragged_kernel(monkeypatch, module, preset):
    """A model that mixes windowed and global layers hands the ragged kernel
    its window as an operand: its mixed step traces (and every start lowers)
    ONE instance, where a static window under a ``lax.cond`` held two; and
    beside it ONE decode-kernel instance for the step's one-query rows
    (ISSUE 44), which takes the same operand (the hybrid's cross layers hold
    one more, as before)."""
    import importlib

    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    # The name ops/paged_attention.py bound at import: the dispatch takes the kernel.
    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    model = importlib.import_module(f"inference_gateway_tpu.models.{module}")
    cfg = model.PRESETS[preset]
    slots, page, seq, T = 4, 16, 64, 40
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), cfg))
    if module == "phi4flash":
        cache = jax.eval_shape(lambda: model.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq)))
    else:
        pages = jax.ShapeDtypeStruct((cfg.num_layers, slots * seq // page, page, cfg.num_kv_heads * cfg.hd), BF16)
        cache = {"k": pages, "v": pages}
    tokens, row = jax.ShapeDtypeStruct((1, T), I32), jax.ShapeDtypeStruct((slots,), I32)
    traced = jax.make_jaxpr(lambda *a: model.forward_ragged(a[0], cfg, *a[1:]))(
        params, tokens, tokens, cache, tokens, jax.ShapeDtypeStruct((slots, seq // page), I32), row, row, row)
    assert len(_kernel_bodies(traced.jaxpr)) == 1
    assert len(_kernel_bodies(traced.jaxpr, "_paged_attn_kernel")) == (2 if module == "phi4flash" else 1)


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


# (sizes L, E, H, I, top-k; tokens): SmallThinker's stage at a decode step and a
# mixed step, Mixtral-8x7B at a mixed step of chip_smoke.py's budget; Trinity-Mini's
# and Keye's expert layers at their cells' mixed step and decode step (ISSUE 42).
SMALLTHINKER, MIXTRAL = (16, 64, 2560, 768, 6), (32, 8, 4096, 14336, 2)
TRINITY, KEYE = (6, 128, 2048, 1024, 8), (8, 128, 2048, 768, 8)
EXPERT_KERNEL_EQUATIONS = 190
EXPERT_LAYERS = {
    "decode_48_rows": (SMALLTHINKER, 48), "mixed_step_560_tokens": (SMALLTHINKER, 560),
    "trinity_mixed_step_544_tokens": (TRINITY, 544), "trinity_decode_32_rows": (TRINITY, 32),
    "keye_mixed_step_524_tokens": (KEYE, 524), "keye_decode_12_rows": (KEYE, 12),
}


def _expert_layer(sds, sizes, tokens):
    """(fn, shapes) of one dropless layer over int8 stacks; ``sds(shape, dtype)``."""
    from inference_gateway_tpu.ops.moe import moe_dropless
    from inference_gateway_tpu.ops.quant import QTensor

    L, E, H, I, K = sizes

    def stack(k, n):
        return QTensor(sds((L, E, k, n), jnp.int8), sds((L, E, 1, n), jnp.float32))

    return (lambda x, logits, wg, wu, wd, li: moe_dropless(
        x, logits, K, wg, wu, wd, li, act="relu", valid=jnp.arange(tokens) < tokens - 3)), (
        sds((tokens, H), BF16), sds((tokens, E), jnp.float32), stack(H, I), stack(H, I), stack(I, H), sds((), I32))


@pytest.mark.parametrize("case", list(EXPERT_LAYERS))
def test_dropless_expert_layer_compiles_for_v5e_with_no_copy_of_the_stack(one_chip, as_on_tpu, case):
    """An expert layer at its published widths (SmallThinker: 64 experts of
    2560 x 768, top-6, int8 stacks of the 16-layer stage; Trinity-Mini and Keye:
    128 experts of 2048 x 1024 / 768, top-8) through the chip's compiler: both
    grouped matmuls are kernels, the stacks reach them whole (neither a bf16
    copy of a layer's experts, which ``jax.lax.ragged_dot`` on the dequantised
    layer makes, nor an int8 slice of a leaf), and the temporaries are the
    rows', a few MB."""
    sizes, tokens = EXPERT_LAYERS[case]
    _, E, H, I, _ = sizes
    fn, shapes = _expert_layer(one_chip, sizes, tokens)
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "moe_gate_up_gmm" in text and "moe_down_gmm" in text
    assert f"bf16[{E},{H},{I}]" not in text and f"s8[1,{E},{H},{I}]" not in text and f"s8[{E},{H},{I}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("case", list(EXPERT_LAYERS))
def test_expert_kernels_stay_inside_their_start_up_budget(as_on_tpu, case):
    """No clock: the traced equations of the two kernels' bodies, which every
    start of a step program pays for (the compile cache saves the compile, not
    the trace and lowering). The parent's bodies held 46 + 37; a visit of
    several granules unrolls its copies over them (105 + 96 at two granules
    a visit, 171 + 162 at four): EXPERT_KERNEL_EQUATIONS leaves a tenth."""
    fn, shapes = _expert_layer(jax.ShapeDtypeStruct, *EXPERT_LAYERS[case])
    jaxpr = jax.make_jaxpr(fn)(*shapes).jaxpr
    for name in ("moe_gate_up_gmm", "moe_down_gmm"):
        (body,) = _kernel_bodies(jaxpr, name)
        assert _equations(body) <= EXPERT_KERNEL_EQUATIONS


# ISSUE 62: a step's rows into both flat pools by ``ops/pool_write.py``'s kernel, at each
# cell's mixed-step shape (rows, lanes of a row, the pool's rows) and at 64 decode rows (a
# shape the dispatch leaves to the scatter: the kernel must still compile there). One
# body whatever the shape: no loop is unrolled over rows or lanes.
POOL_WRITES = {
    "evabyte_517_rows_of_8_KB": (517, 4096, 430080), "mistral_536_rows_of_2_KB": (536, 1024, 1572864),
    "qwen2.5_560_rows_of_1_KB": (560, 512, 28 * 48 * 2048), "trinity_544_rows_of_1_KB": (544, 512, 8 * 32 * 12800),
    "lfm2_576_rows_of_1_KB": (576, 512, 6 * 64 * 4096), "decode_64_rows_of_1_KB": (64, 512, 6 * 64 * 4096),
}
POOL_WRITE_EQUATIONS = 660


def _pool_write(sds, rows, width, pool_rows):
    from inference_gateway_tpu.ops.pool_write import write_rows_tpu

    pool, step = sds((pool_rows, width), BF16), sds((rows, width), BF16)
    return (lambda ck, cv, k, v, idx: write_rows_tpu((ck, cv), (k, v), idx)), (pool, pool, step, step, sds((rows,), I32))


@pytest.mark.parametrize("case", list(POOL_WRITES))
def test_pool_write_kernel_compiles_for_v5e_with_no_copy_of_a_pool(one_chip, case):
    """Both pools through ONE kernel, aliased through the call: the compiled module
    holds no copy of a pool and no scatter, and its temporaries are the indices'."""
    rows, width, pool_rows = POOL_WRITES[case]
    fn, shapes = _pool_write(one_chip, rows, width, pool_rows)
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "pool_write_kernel" in text
    assert "scatter" not in text
    assert not re.search(rf"= bf16\[{pool_rows},{width}\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("case", list(POOL_WRITES))
def test_pool_write_kernel_stays_inside_its_start_up_budget(case):
    """No clock: the traced equations of the kernel's body and of what stands around it
    (the indices' bound; EvaByte's 517 rows padded to whole tiles of 8)."""
    fn, shapes = _pool_write(jax.ShapeDtypeStruct, *POOL_WRITES[case])
    jaxpr = jax.make_jaxpr(fn)(*shapes).jaxpr
    (body,) = _kernel_bodies(jaxpr, "pool_write_kernel")
    assert _equations(body) <= POOL_WRITE_EQUATIONS
    assert _equations(jaxpr) - _equations(body) <= 40


@pytest.mark.parametrize("module,preset", [("llama", "test-tiny"), ("mixtral", "lfm2-test-tiny")])
def test_on_the_chip_a_mixed_step_holds_one_pool_write_kernel_a_traced_body_and_the_decode_step_the_scatter(
        monkeypatch, as_on_tpu, module, preset):
    """What the step programs hold where ``pool_write_dispatch`` sees a TPU (the census
    below traces them as this sandbox does, off the chip: the scatter). The presets at a
    head size that puts a row of the pool on the 128 lanes: a mixed step of 160 rows
    writes both pools by ONE kernel a traced layer body, beside its ragged kernel, and
    scatters into no pool; a decode step's 4 rows keep the two scatters. A model of two
    kinds (LFM2) hands the same kernel its state layers' skipped rows."""
    import dataclasses
    import importlib

    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    model = importlib.import_module(f"inference_gateway_tpu.models.{module}")
    cfg = dataclasses.replace(model.PRESETS[preset], head_dim=64)
    assert cfg.num_kv_heads * cfg.hd == 128
    slots, page, seq, T = 4, 16, 256, 160
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), cfg))
    if module == "llama":
        pages = jax.ShapeDtypeStruct((cfg.num_layers, slots * seq // page, page, 128), BF16)
        cache = {"k": pages, "v": pages}
    else:
        cache = jax.eval_shape(lambda: model.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq), dtype=BF16))
    sds = jax.ShapeDtypeStruct
    tokens, row, col, table = sds((1, T), I32), sds((slots,), I32), sds((slots, 1), I32), sds((slots, seq // page), I32)
    mixed = jax.make_jaxpr(lambda *a: model.forward_ragged(a[0], cfg, *a[1:]))(
        params, tokens, tokens, cache, tokens, table, row, row, row)
    decode = jax.make_jaxpr(lambda *a: model.forward_paged(a[0], cfg, *a[1:], mode="decode"))(
        params, col, col, row, cache, col, table)
    writes = _kernel_bodies(mixed.jaxpr, "pool_write_kernel")
    assert len(writes) == len(_kernel_bodies(mixed.jaxpr)) >= 1
    assert _census(mixed.jaxpr, {}).get("scatter", 0) == STATE_SCATTERS.get(preset, 0)
    assert not _kernel_bodies(decode.jaxpr, "pool_write_kernel") and _census(decode.jaxpr, {})["scatter"] == 2


def _values(jaxpr):
    """Every value a jaxpr makes, loops, branches and jitted calls included; never a kernel's own."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        if eqn.primitive.name != "pallas_call":
            for inner in _inner_jaxprs(eqn):
                yield from _values(inner)


# ISSUE 51. (tokens, experts, hidden, expert width, top-k) of the two mixed steps whose top-k is not 8.
COMBINE_SHAPES = {"granite_top_10": (544, 72, 4096, 768, 10), "xing_top_4": (544, 64, 3584, 1024, 4)}


@pytest.mark.parametrize("case", list(COMBINE_SHAPES))
def test_the_combine_holds_no_float32_copy_of_the_chosen_rows_and_never_k_on_a_tiled_axis(as_on_tpu, case):
    """No clock: the values ``_experts_of_shard`` makes. The parent's combine
    wrote the gathered rows as float32 ``(N*k, H)`` and reshaped them to
    ``(N, k, H)``, where top-10 pads each token's block to 16 sublanes and
    top-4 to 8: 4-5 times the chip's time per element of top-8 (``PERF.md``
    section 6, PR 51). The largest float32 value is now the ``(N, H)`` sum,
    and a value as large as the gathered rows has k on no minor axis."""
    from inference_gateway_tpu.ops.moe import _experts_of_shard
    from inference_gateway_tpu.ops.quant import QTensor

    N, E, H, I, k = COMBINE_SHAPES[case]
    sds = jax.ShapeDtypeStruct

    def stack(kk, n):
        return QTensor(sds((2, E, kk, n), jnp.int8), sds((2, E, 1, n), jnp.float32))

    jaxpr = jax.make_jaxpr(lambda x, w, e, wg, wu, wd, li: _experts_of_shard(
        x, w, e, wg, wu, wd, li, act="silu", kernel=None, ep=None, tp=None))(
        sds((N, H), BF16), sds((N, k), jnp.float32), sds((N * k,), I32), stack(H, I), stack(H, I), stack(I, H),
        sds((), I32)).jaxpr
    assert all(len(_kernel_bodies(jaxpr, name)) == 1 for name in ("moe_gate_up_gmm", "moe_down_gmm"))
    large = [v for v in _values(jaxpr) if v.size >= N * k * H]
    assert any(v.shape == (k, N, H) and v.dtype == BF16 for v in large)  # the one gather, choice-major
    assert not [v for v in large if v.dtype == jnp.float32]
    assert not [v for v in large if k in v.shape[-2:]]
    assert max(v.size for v in _values(jaxpr) if v.dtype == jnp.float32) <= N * H


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


@pytest.mark.parametrize("form", ["decode", "ragged", "definition"])
def test_state_operations_compile_for_v5e_at_the_published_widths(one_chip, as_on_tpu, form):
    """The convolution and the selective scan of one Mamba layer (ops/ssm.py) at
    64 slots x (16, 5,120) float32, the channels in the lanes: the batched decode
    update; the mixed step's form over 576 packed tokens (ISSUE 64: the chunk
    kernel ``ssm_scan_chunk_kernel`` for the rows of more than one token beside the
    one-token update, the store whole and in place); and the token loop that is
    their definition. A pass says the chip's compiler takes them and holds the
    store once (no second copy of it)."""
    from inference_gateway_tpu.ops import ssm

    S, T, Di, N, K = PHI["slots"], PHI["budget"], PHI["di"], PHI["n"], PHI["k"]
    F32 = jnp.float32
    states, tails = one_chip((S, N, Di), F32), one_chip((S, K - 1, Di), F32)
    w, b, A, D = one_chip((K, Di), F32), one_chip((Di,), F32), one_chip((N, Di), F32), one_chip((Di,), F32)
    row = one_chip((S,), I32)
    packed = (one_chip((T, Di), BF16), tails, w, b, states, one_chip((T, Di), F32), one_chip((T, N), F32),
              one_chip((T, N), F32), A, D, row, row, row, one_chip((T,), I32))
    if form == "decode":
        def step(u, tails, w, b, states, delta, B, C, A, D, valid):
            c, tails = ssm.conv_decode(u, tails, w, b, valid)
            return ssm.scan_decode(states, delta, c, B, C, A, D, valid), tails

        args = (one_chip((S, Di), BF16), tails, w, b, states, one_chip((S, Di), F32), one_chip((S, N), F32),
                one_chip((S, N), F32), A, D, one_chip((S,), jnp.bool_))
    elif form == "ragged":
        def step(u, tails, w, b, states, delta, B, C, A, D, starts, lens, kv, positions):
            scan, seg, fresh, _ = ssm.ragged_scan(starts, lens, kv, T)
            c, tails = ssm.conv_ragged(u, tails, w, b, seg, fresh, starts, lens)
            y, store = scan(states[None], 0, delta, c, B, C, A, D)
            return (y, store[0]), tails

        args = packed
    else:
        def step(u, tails, w, b, states, delta, B, C, A, D, starts, lens, kv, positions):
            seg = ssm.token_segments(starts, lens, T)
            c, tails = ssm.conv_ragged(u, tails, w, b, seg, (kv - lens) == 0, starts, lens)
            return ssm.scan_ragged(states, delta, c, B, C, A, D, seg, positions, jnp.sum(lens)), tails

        args = packed
    compiled = jax.jit(step, donate_argnums=(1, 4)).lower(*args).compile()
    store = S * Di * N * 4
    assert compiled.memory_analysis().temp_size_in_bytes < store + (64 << 20)
    assert ("ssm_scan_chunk_kernel" in compiled.as_text()) == (form == "ragged")


# ISSUE 64: Jamba2-3B's shapes. The chunk kernel alone at a mixed step of 544 tokens over a store of 26 layers x 32
# slots x (16, 5,120); both paged kernels at 20 query heads over ONE KV head of 128, 100 pages of 128 a row.
JAMBA = dict(slots=32, budget=544, di=5120, n=16, layers=26, hq=20, hkv=1, d=128, pages=100)


def test_selective_scan_chunk_kernel_compiles_for_v5e_at_jamba2_widths(one_chip):
    from inference_gateway_tpu.ops import ssd, ssm

    S, T, Di, N, L = (JAMBA[k] for k in ("slots", "budget", "di", "n", "layers"))
    F32 = jnp.float32
    visits = ssd.max_visits(T, S, ssm.CHUNK_TOKENS)
    compiled = jax.jit(
        lambda store, o, d, c, B, C, A, D, meta: ssm.scan_chunk_tpu(store, o, d, c, B, C, A, D, meta, interpret=False),
        donate_argnums=(0,)).lower(
        one_chip((L, S, N, Di), F32), one_chip((), I32), one_chip((T, Di), F32), one_chip((T, Di), F32),
        one_chip((T, N), F32), one_chip((T, N), F32), one_chip((N, Di), F32), one_chip((Di,), F32),
        one_chip((visits, ssd.META), I32)).compile()
    text = compiled.as_text()
    assert "ssm_scan_chunk_kernel" in text
    assert not re.findall(rf"= f32\[{L},{S},{N},{Di}\]\S* copy\(", text), "the state store is copied"
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20     # B's and C's columns and the padded operands, no store


@pytest.mark.parametrize("slots,layers", [(JAMBA["slots"], JAMBA["layers"]), (PHI["slots"], 9)], ids=["jamba2", "phi4flash"])
def test_selective_scan_one_token_kernel_compiles_for_v5e(one_chip, slots, layers):
    """``ssm_scan_decode_kernel`` at both Mamba-1 models' stores: the walk of the rows that advance over (16, 5,120)
    float32 states, the store in place (no copy of it, no second one among the temporaries)."""
    from inference_gateway_tpu.ops import ssm

    Di, N, F32 = JAMBA["di"], JAMBA["n"], jnp.float32
    row = one_chip((slots,), jnp.bool_)
    compiled = jax.jit(
        lambda store, o, d, c, B, C, A, D, valid, fresh: ssm.scan_decode_tpu(store, o, d, c, B, C, A, D, valid, fresh),
        donate_argnums=(0,)).lower(
        one_chip((layers, slots, N, Di), F32), one_chip((), I32), one_chip((slots, Di), F32), one_chip((slots, Di), F32),
        one_chip((slots, N), F32), one_chip((slots, N), F32), one_chip((N, Di), F32), one_chip((Di,), F32), row, row).compile()
    text = compiled.as_text()
    assert "ssm_scan_decode_kernel" in text
    assert not re.findall(rf"= f32\[{layers},{slots},{N},{Di}\]\S* copy\(", text), "the state store is copied"
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("kernel", ["decode", "ragged"])
def test_paged_kernels_compile_at_twenty_queries_over_one_kv_head_for_v5e(one_chip, kernel):
    """The first group wider than a tile's 8 sublanes and not a multiple of them, the first pool of ONE KV
    head: no pad of the group is needed (on the chip both agree with the gather path: PERF.md section 6, PR 64)."""
    S, T, Hq, Hkv, Dh, maxp = (JAMBA[k] for k in ("slots", "budget", "hq", "hkv", "d", "pages"))
    pool = one_chip((2 * S * maxp, PAGE, Hkv * Dh), BF16)
    row, table = one_chip((S,), I32), one_chip((S, maxp), I32)
    if kernel == "decode":
        _compiles_to_a_kernel(lambda q, k, v, t, l: paged_attention_tpu(q, k, v, t, l, Hkv, interpret=False),
                              one_chip((S, Hq, Dh), BF16), pool, pool, table, row)
    else:
        _compiles_to_a_kernel(
            lambda q, k, v, t, s, l, kv: ragged_paged_attention_tpu(q, k, v, t, s, l, kv, Hkv, interpret=False),
            one_chip((T, Hq, Dh), BF16), pool, pool, table, row, row, row)


# ISSUE 53: the packed convolution alone at the three state cells' mixed shapes (packed tokens, channels, slots,
# taps, bias and activation as the model calls it).
CONV_SHAPES = {"granite": (544, 8448, 32, 4, True), "phi4flash": (PHI["budget"], PHI["di"], PHI["slots"], PHI["k"], True),
               "lfm2": (576, 2048, 64, 3, False)}


@pytest.mark.parametrize("cell", list(CONV_SHAPES))
def test_packed_convolution_compiles_for_v5e_without_a_tokens_by_channels_gather(one_chip, cell):
    """``ops/ssm.conv_ragged`` through the chip's compiler: the guard against the
    form coming back that gathered a float32 ``[tokens, channels]`` array of
    remembered inputs for every lag (three gathers of 18 MB at Granite's 544 x
    8,448, 392 us a layer where the operator's bytes take 42). The compiled
    program holds no gather with such a result, inside a fusion or out, and its
    entry makes at most two float32 ``[tokens, channels]`` arrays besides the
    result (the result is the body's rows with the segments' first rows
    scattered over them, in place)."""
    import re

    from inference_gateway_tpu.ops import ssm

    T, C, S, K, bias = CONV_SHAPES[cell]
    F32 = jnp.float32
    row = one_chip((S,), I32)

    def step(u, tails, w, b, starts, lens, kv):
        seg = ssm.token_segments(starts, lens, T)
        return ssm.conv_ragged(u, tails, w, b if bias else None, seg, (kv - lens) == 0, starts, lens,
                               act=jax.nn.silu if bias else None)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        one_chip((T, C), BF16), one_chip((S, K - 1, C), F32), one_chip((K, C), F32), one_chip((C,), F32), row, row, row).compile()
    text = compiled.as_text()
    assert not re.findall(rf"= f32\[{T},{C}\]\S* gather\(", text), "a [tokens, channels] gather"
    entry = text[text.index("ENTRY"):]
    wide = [op for op in re.findall(rf"= f32\[{T},{C}\]\S* ([\w\-]+)\(", entry)
            if op not in ("parameter", "bitcast", "get-tuple-element", "copy-start", "copy-done")]
    assert len(wide) <= 3, wide


# What each family's two step programs trace to, at its tiny preset with the
# kernels' dispatch steered to the TPU's: (kernel instances, ``cond``s,
# equations) of the mixed step and of the decode step. The five older families
# read exactly what the parent's tree reads (PR 36 added an attention seam to
# the shared skeletons: a model without one must not pay for it in trace,
# lowering or start-up); the model with the indexer holds NO attention kernel
# (its one path scores, selects and reads in plain XLA whatever the rows hold: a
# branch to the paged kernels for rows at or under topk would run only in
# warm-up and cost every start an instance), in the mixed step two
# ``cond``s (a tile that sees topk or fewer selects nothing; a tie at the cut) and
# the tile loops, under a ceiling of a tenth over what it reads (923 / 546).
# ISSUE 37: the decode kernel takes the window as an operand, so the decode steps
# of the two models that mix kinds of layer hold one instance fewer and no
# ``cond``; and its launch reshapes q and the output by KV head and pads the
# table and the lengths by the slot that ends the walk: three equations more
# around every decode kernel (the hybrid's mixed step holds one too, for its
# cross layers).
# ISSUE 41: the mixture with a dense prefix scans TWO groups of layers (prefix,
# body) over one page pool, so each of its step programs holds one kernel
# instance a group (the window an operand in both: no ``cond``) and its
# equations are two layer bodies'; the five older rows are unedited.
# ISSUE 42: the expert layer lays its rows out from granule boundaries (a
# sort of pairs and fillers, and a second that turns it back) and makes its
# visit table without a gather: 35-37 equations more a step program in the
# four mixtures (the one with a dense prefix holds one expert layer in its
# two layer bodies), none in the other three rows.
# ISSUE 43: the mixture with convolution layers scans two groups too, but its
# prefix holds convolution layers alone (no kernel, no branch) and its body TWO
# ``cond``s on the layer's flag (the projections or nothing; the kernel and its
# output projection or the convolution: the pools are written between them and
# never pass through one), so each of its step programs holds one kernel
# instance for six attention layers; the seven older rows are unedited (the
# seam is a Python branch they do not take).
# ISSUE 44: a MIXED step's attention is two launches a scanned group, the ragged
# kernel and, for the one-query rows of the packed prefix, the decode kernel:
# one kernel instance more a group in every row that holds the ragged kernel
# (the hybrid's three: its cross layers held a decode kernel already), and
# 40-42 equations more a group around them (which packed positions hold a
# one-query row, found by comparison; their table rows and lengths; the decode
# kernel's launch; one addition that joins the outputs: no ``cond``, and no
# scatter but the keys' and the values' writes). The decode steps' entries are
# unedited, and so is the row of the model with the indexer.
# ISSUE 45: the model with the indexer alone: its one-query rows' read is the decode
# kernel under a mask where a row of the page table holds at most WALK_UNDER x topk
# tokens (this layout's 64 against 288), ONE instance in each step program, and the
# selection before it one more ``cond`` (a tie at the cut: ``topk_mask``) where the
# sort and the two token gathers went: 921 and 545 equations, from 1,015 and 600
# (ceilings, as before: the row's counts move with the sparse branch's every edit).
# The eight other rows are unedited.
# ISSUE 51: the expert layer's combine sums a token's choices one after another
# (slice, convert, select, weight, add: the order is the program's, not the
# compiler's) over tokens padded to whole tiles, where one einsum stood: 32
# equations more an expert layer body at the presets' top-2 (48 at
# SmallThinker's top-3: 16 a choice) in both step programs of the five
# mixtures, all elementwise; the indexer's ceilings move by the same 32 (953 /
# 577 read). The three rows without an expert layer are unedited.
# ISSUE 53: the packed convolution of a state layer (``ops/ssm.conv_ragged``) takes a
# token's lagged inputs from its packed neighbours by static shifts and only a
# segment's first K - 1 rows from the slot's tail: no gather of ``[tokens, channels]``
# a lag (3-4 gathers fewer a traced body: ONE is left, of the packed rows the heads
# and the new tails read), one row scatter more (the heads written over the body's
# rows: ``STATE_SCATTERS``), and the sums spelled out slab by slab (K - 1 heads of K
# taps, the new tails by selects where a gather stood): 65 equations more a traced
# body at K = 4 (the hybrid's one, Granite's two), 26 at LFM2's K = 3 (two
# bodies), all elementwise but the scatter; mixed steps alone,
# every decode step's entry is unedited. Granite's preset joins the census here
# (its two runs of Mamba layers are two traced bodies; 2,406 / 1,269 on the
# parent). The six rows without a state layer are unedited.
STEP_PROGRAMS = {
    ("llama", "test-tiny"): ((2, 0, 196), (1, 0, 145)),
    ("llama", "qwen2-test-tiny"): ((2, 0, 203), (1, 0, 152)),
    ("mixtral", "mixtral-test-tiny"): ((2, 0, 391), (1, 0, 334)),
    ("mixtral", "smallthinker-test-tiny"): ((2, 0, 426), (1, 0, 369)),
    ("phi4flash", "phi4flash-test-tiny"): ((3, 0, 1492), (2, 0, 782)),   # (ISSUE 64: the mixed step holds the chunk form, its twin here, for the loop)
    ("mixtral", "keye-test-tiny"): ((1, 3, 960), (1, 1, 580)),
    ("mixtral", "trinity-test-tiny"): ((4, 0, 701), (2, 0, 594)),
    ("mixtral", "lfm2-test-tiny"): ((2, 2, 919), (1, 2, 579)),
    # (ISSUE 65) the three mixed steps whose rows of more than one token walk ``ops/ssd.block_plan`` count the plan's
    # static bound beside its live visits: two equations each (the constant, converted and joined to the counts)
    ("mixtral", "granite4-test-tiny"): ((2, 0, 2538), (1, 0, 1269)),
    # (ISSUE 54) six traced bodies (the dense layer, K K, M, K K K, M), no branch; the chunk form, the update and the
    # latent read are their twins off the TPU (``ops.mla_attention`` and ``utils.platform`` are not steered here)
    ("mixtral", "kimi-linear-test-tiny"): ((0, 0, 3847), (0, 0, 1995)),
    # (ISSUE 61) test-tiny's two programs and, in front of the layers, the fold: a loop over the rows that start a
    # window (its trip count their number) around a loop over the layers, no ``cond`` and no scatter (the summaries
    # are written where a page lies); the residual sums in float32; the counts. No row above is edited.
    ("llama", "evabyte-test-tiny"): ((2, 0, 472), (1, 0, 396)),
}
# A mixed step's scatters beyond the pools' writes: the convolution's heads, one a traced state-layer body (ISSUE
# 53); Granite's two runs of Mamba layers also place their one-token rows' results and update the store at them.
# (ISSUE 64) the hybrid's one traced pair also places its one-token rows' results and, off the TPU, the chunk
# form's twin puts a row's last state back.
STATE_SCATTERS = {"phi4flash-test-tiny": 3, "lfm2-test-tiny": 2, "granite4-test-tiny": 6}


def _census(jaxpr, out):
    """Primitive -> count under ``jaxpr``; a kernel's own body is not entered."""
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
        if eqn.primitive.name != "pallas_call":
            for inner in _inner_jaxprs(eqn):
                _census(inner, out)
    return out


@pytest.mark.parametrize("module,preset", list(STEP_PROGRAMS), ids=[p for _, p in STEP_PROGRAMS])
def test_step_programs_hold_the_kernels_and_equations_they_held(monkeypatch, module, preset):
    import importlib

    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    model = importlib.import_module(f"inference_gateway_tpu.models.{module}")
    cfg = model.PRESETS[preset]
    slots, page, seq, T = 4, 16, 64, 40
    page = getattr(model.family(cfg).cached_rows, "page_size", page)   # (a pool laid out for one page size: ISSUE 61)
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), cfg))
    own_pool = module == "phi4flash" or getattr(cfg, "indexer_topk", 0) or getattr(cfg, "layer_conv", None)
    if own_pool:
        cache = jax.eval_shape(lambda: model.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq)))
    else:
        pages = jax.ShapeDtypeStruct((cfg.num_layers, slots * seq // page, page, cfg.num_kv_heads * cfg.hd), BF16)
        cache = {"k": pages, "v": pages}
    sds = jax.ShapeDtypeStruct
    tokens, row, col, table = sds((1, T), I32), sds((slots,), I32), sds((slots, 1), I32), sds((slots, seq // page), I32)
    mixed = jax.make_jaxpr(lambda *a: model.forward_ragged(a[0], cfg, *a[1:]))(
        params, tokens, tokens, cache, tokens, table, row, row, row)
    decode = jax.make_jaxpr(lambda *a: model.forward_paged(a[0], cfg, *a[1:], mode="decode"))(
        params, col, col, row, cache, col, table)
    older = preset != "keye-test-tiny"
    for traced, (kernels, conds, equations) in zip((mixed, decode), STEP_PROGRAMS[(module, preset)]):
        census = _census(traced.jaxpr, {})
        assert (census.get("pallas_call", 0), census.get("cond", 0)) == (kernels, conds)
        if traced is mixed and kernels and older:  # the two outputs are joined without one: the pools' writes alone
            assert census["scatter"] == 2 * len(_kernel_bodies(traced.jaxpr)) + STATE_SCATTERS.get(preset, 0), census["scatter"]
        total = sum(census.values())
        assert (total == equations if older else total <= equations), total


# ISSUE 52: every equation of every family's two step programs stands under a name of
# the closed vocabulary of scopes (``bench/scope_classes.json``; the rule and the list:
# docs/observability.md, "Device time by named scope"), so that a capture's device time
# can be read by scope. What may stand under none, a closed list by primitive: the
# program's own ``jit``, a loop's own equation (``scan``, ``while``: its self time on the
# device is what its body does not cover), the pools' and stacks' metadata-only views
# (``reshape``, ``squeeze``, ``slice`` of a run's leaves, ``broadcast_in_dim`` of a row
# vector to a column) and the layer indices a scan walks (``iota``, ``add``). The
# count is held too: the two families beyond the census's eight (Xing's streams,
# Granite's runs of one kind) read the most, 8 and 22.
UNSCOPED_PRIMITIVES = {"jit", "scan", "while", "reshape", "squeeze", "slice", "broadcast_in_dim", "iota", "add"}
SCOPED_PROGRAMS = list(STEP_PROGRAMS) + [("mixtral", "xing4-test-tiny")]


def _scope_vocabulary() -> dict:
    import json
    from pathlib import Path

    spec = json.loads((Path(__file__).resolve().parents[1] / "bench" / "scope_classes.json").read_text())
    return {scope: cls for cls, scopes in spec["classes"].items() for scope in scopes}


def _unscoped(jaxpr, vocabulary, prefix="", out=None):
    """(primitive, name stack) of every equation under ``jaxpr`` whose name stack, read
    from the innermost component out, holds no name of ``vocabulary``; and the classes
    seen. A kernel's own body is not entered: on the device it is one event."""
    out = {"unscoped": [], "classes": set()} if out is None else out
    for eqn in jaxpr.eqns:
        stack = f"{prefix}/{eqn.source_info.name_stack}"
        scope = next((part for part in reversed(stack.split("/")) if part in vocabulary), None)
        if scope is None:
            out["unscoped"].append((eqn.primitive.name, stack))
        else:
            out["classes"].add(vocabulary[scope])
        if eqn.primitive.name != "pallas_call":
            for inner in _inner_jaxprs(eqn):
                _unscoped(inner, vocabulary, stack, out)
    return out


@pytest.mark.parametrize("module,preset", SCOPED_PROGRAMS, ids=[p for _, p in SCOPED_PROGRAMS])
def test_every_equation_of_both_step_programs_stands_under_a_scope_of_the_closed_list(monkeypatch, module, preset):
    """The programs the census above traces (the same shapes and the same steering, so
    the second trace of a program is JAX's cached one), read for their name stacks."""
    import importlib

    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    model = importlib.import_module(f"inference_gateway_tpu.models.{module}")
    cfg = model.PRESETS[preset]
    slots, page, seq, T = 4, 16, 64, 40
    page = getattr(model.family(cfg).cached_rows, "page_size", page)   # (a pool laid out for one page size: ISSUE 61)
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), cfg))
    own_pool = (module == "phi4flash" or getattr(cfg, "indexer_topk", 0) or getattr(cfg, "layer_conv", None)
                or getattr(cfg, "kv_lora_rank", 0))
    if own_pool:
        cache = jax.eval_shape(lambda: model.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq)))
    else:
        pages = jax.ShapeDtypeStruct((cfg.num_layers, slots * seq // page, page, cfg.num_kv_heads * cfg.hd), BF16)
        cache = {"k": pages, "v": pages}
    sds = jax.ShapeDtypeStruct
    tokens, row, col, table = sds((1, T), I32), sds((slots,), I32), sds((slots, 1), I32), sds((slots, seq // page), I32)
    mixed = jax.make_jaxpr(lambda *a: model.forward_ragged(a[0], cfg, *a[1:]))(
        params, tokens, tokens, cache, tokens, table, row, row, row)
    decode = jax.make_jaxpr(lambda *a: model.forward_paged(a[0], cfg, *a[1:], mode="decode"))(
        params, col, col, row, cache, col, table)
    vocabulary = _scope_vocabulary()
    for traced in (mixed, decode):
        got = _unscoped(traced.jaxpr, vocabulary)
        assert {p for p, _ in got["unscoped"]} <= UNSCOPED_PRIMITIVES, sorted(
            (p, s) for p, s in got["unscoped"] if p not in UNSCOPED_PRIMITIVES)
        assert len(got["unscoped"]) <= (36 if preset == "kimi-linear-test-tiny" else 24), len(got["unscoped"])
        assert {"attention", "head_sampling", "step"} <= got["classes"], got["classes"]
        assert ("moe" in got["classes"]) == (module == "mixtral")
        assert ("state" in got["classes"]) == (preset in ("phi4flash-test-tiny", "lfm2-test-tiny", "granite4-test-tiny",
                                                           "kimi-linear-test-tiny"))
        assert ("residual" in got["classes"]) == (preset == "xing4-test-tiny")


def test_every_equation_of_the_engines_two_step_programs_stands_under_a_scope_too():
    """The programs as the engine dispatches them, the model's forward inside: the
    packed descriptors' unpacking, the step's key, the bias and the draw, the chunk's
    loop with its flags and budgets, the block the fetch reads."""
    from inference_gateway_tpu.serving.engine import STOP_TABLE_WIDTH, Engine, EngineConfig

    slots = 4
    engine = Engine(EngineConfig(model="test-tiny", attention="paged", page_size=16, max_slots=slots, max_seq_len=256,
                                 dtype="float32", use_mesh=False, mixed_step=True, decode_chunk=4))
    automaton, mnext, mbits, mterm, mbias = engine._mask_args_ee()
    mixed = jax.make_jaxpr(lambda *a: Engine._mixed_step_fn(engine, *a, automaton=False))(
        engine.params, engine.cache, jnp.zeros((engine._mixed_pack.size,), I32), engine._rng, engine._last_block,
        mnext, mbits, mbias)
    row, real, flag = jnp.zeros((slots,), I32), jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), bool)
    decode = jax.make_jaxpr(lambda *a: Engine._decode_chunk_fn_paged_ee(
        engine, *a[:14], mstates=a[14], mnext=a[15], mbits=a[16], mterm=a[17], mbias=a[18], n_steps=4,
        automaton=False))(
        engine.params, engine.cache, row, row, flag, row, jnp.zeros((slots, STOP_TABLE_WIDTH), I32),
        jnp.zeros((slots, 256 // 16), I32), row, real, real, row, flag, engine._rng,
        jnp.asarray(engine._zero_mstates), mnext, mbits, mterm, mbias)
    vocabulary = _scope_vocabulary()
    for traced in (mixed, decode):
        got = _unscoped(traced.jaxpr, vocabulary)
        assert {p for p, _ in got["unscoped"]} <= UNSCOPED_PRIMITIVES, got["unscoped"]
        assert len(got["unscoped"]) <= 12, got["unscoped"]
        assert {"attention", "mlp", "head_sampling", "step"} <= got["classes"]


@pytest.mark.parametrize("form", ["decode", "ragged"])
def test_sparse_attention_compiles_for_v5e_at_the_published_widths(one_chip, monkeypatch, form):
    """Score, select and read (ops/sparse_attention.py) for Keye-VL-2.0's stage:
    12 rows of 100 pages, 16 indexer heads of 64, top-2,048 of up to 12,800,
    32 / 4 heads of 128; a decode step's rows, and a 524-token mixed step's.
    Plain XLA but for the one-query rows' read: a pass says the chip's compiler
    takes it inside 1 GB of temporaries beside the 2.7 GB pool (no copy of a
    pool)."""
    from inference_gateway_tpu.ops import sparse_attention as sa

    # The name ops/paged_attention.py bound at import: the dispatch takes the kernel.
    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")

    slots, pages, layers, T = 12, 100, 8, 524
    tokens = layers * slots * pages * PAGE
    pools = (one_chip((tokens, 4 * D), BF16), one_chip((tokens, 4 * D), BF16), one_chip((tokens // PAGE, 64, PAGE), BF16))
    table, row = one_chip((slots, pages), I32), one_chip((slots,), I32)
    kw = dict(topk=2048, num_kv_heads=4, page_size=PAGE)
    if form == "decode":
        fn = lambda q, qi, wi, k, v, ki, table, lens: sa.sparse_decode(q, qi, wi, k, v, ki, table, lens, **kw)
        args = (one_chip((slots, 32, D), BF16), one_chip((slots, 16, 64), BF16), one_chip((slots, 16), jnp.float32),
                *pools, table, row)
    else:
        fn = lambda q, qi, wi, pos, k, v, ki, table, qs, ql, kl: sa.sparse_ragged(
            q, qi, wi, pos, k, v, ki, table, qs, ql, kl, **kw)
        args = (one_chip((T, 32, D), BF16), one_chip((T, 16, 64), BF16), one_chip((T, 16), jnp.float32),
                one_chip((T,), I32), *pools, table, row, row, row)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    text = compiled.as_text()
    # Rows of 12,800 tokens are under WALK_UNDER x topk: the one-query rows' read is ONE
    # decode-kernel instance under the mask, named for the trace's reader, and no sort.
    assert sa.one_query_read(2048, pages * PAGE) == "walk"
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%dsa_paged_attn_kernel" in text
    assert " sort(" not in text


# The decode kernel under a mask at the long-context cell's layout (ISSUE 45): 12 slots,
# 32 / 4 heads of 128, rows of 104 pages (the cell's 13,312 tokens), the pool of 8
# layers. Its traced body holds 127 equations, 7 more than the unmasked one at that
# layout (120, as qwen2.5-7b's row of DECODE_LAYOUTS, whose heads are the same): the
# start-up guard, as DECODE_LAYOUTS' (a tenth of room).
def _masked_decode_launch(sds, slots=12, pages=104, layers=8):
    pool = sds((layers * slots * pages, PAGE, 4 * D), BF16)
    shapes = [sds((slots, 32, D), BF16), pool, pool, sds((slots, pages), I32), sds((slots,), I32),
              sds((slots, pages * PAGE), jnp.bool_)]
    return (lambda q, k, v, table, lengths, mask: paged_attention_tpu(
        q, k, v, table, lengths, 4, interpret=False, mask=mask, name="dsa_paged_attn_kernel")), shapes


def test_masked_decode_kernel_compiles_for_v5e_at_the_long_context_cells_layout(one_chip):
    fn, shapes = _masked_decode_launch(one_chip)
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%dsa_paged_attn_kernel" in text   # what bench/dsa_bytes.py's pattern finds the event by


def test_masked_decode_kernel_stays_inside_its_start_up_budget():
    fn, shapes = _masked_decode_launch(jax.ShapeDtypeStruct)
    (body,) = _kernel_bodies(jax.make_jaxpr(fn)(*shapes).jaxpr, "dsa_paged_attn_kernel")  # named: its source info begins so
    assert _equations(body) <= 140


# What the engine's two host-stepped programs trace to on the tiny dense
# preset, the model's forward included: the parent's counts (350 and 26) and
# what ISSUE 40's operand adds to each, the unpacking of one more field and
# the one scatter (or select) that puts a flagged row's token where the packed
# one lay. A traced equation costs every start ~0.35 ms here and 3-5 times
# that on the chip's host (``PERF.md`` section 6, PR 34): the whole addition is
# under 0.1 s, and there is no second compiled form to load (held by
# ``tests/test_pipelined_admission.py``). ISSUE 49 adds two to the mixed step,
# traced with the engine's own logit-bias table as every start now traces it:
# the slice that drops the table's zero row and the add (368 -> 370); the
# packed mask's expansion and the automaton's gathers stay out until a grammar.
HOST_STEPPED_PROGRAMS = {"_mixed_step_fn": 350 + 18 + 2, "_fresh_unpack_fn": 26 + 12}


@pytest.mark.parametrize("name", list(HOST_STEPPED_PROGRAMS))
def test_host_stepped_programs_stay_under_their_equation_ceilings_with_the_block_operand(name):
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig

    engine = Engine(EngineConfig(model="test-tiny", attention="paged", page_size=16, max_slots=4, max_seq_len=256,
                                 dtype="float32", use_mesh=False, mixed_step=True, decode_chunk=4))
    block = engine._last_block
    assert block.shape == (2, 4) and block.dtype == jnp.float32
    if name == "_mixed_step_fn":
        automaton, mnext, mbits, mbias = engine._mask_args()
        assert not automaton and mbias.shape == (4 + 1, engine.model_cfg.vocab_size)
        traced = jax.make_jaxpr(lambda *a: Engine._mixed_step_fn(engine, *a, automaton=False))(
            engine.params, engine.cache, jnp.zeros((engine._mixed_pack.size,), I32), engine._rng, block,
            mnext, mbits, mbias)
    else:
        traced = jax.make_jaxpr(lambda *a: Engine._fresh_unpack_fn(engine, *a))(
            jnp.zeros((engine._fresh_pack.size,), I32), engine._rng, block)
    census = _census(traced.jaxpr, {})
    assert sum(census.values()) <= HOST_STEPPED_PROGRAMS[name], census
    # the token's path is one scatter into the packed tokens (mixed) or one select (fresh): no branch
    assert census.get("cond", 0) == 0


@pytest.mark.parametrize("form", ["mixed", "decode"])
def test_lfm2_step_programs_compile_for_v5e_at_the_published_widths(topo, monkeypatch, as_on_tpu, form):
    """LFM2-8B-A1B whole (ISSUE 43: 24 layers, 18 gated short convolutions beside
    6 GQA layers of 32 / 8 heads of 64, 32 experts of 1,792 top-4) as its cell
    serves it: 64 slots of 16 pages of 128, a mixed step of 576 packed tokens and
    a decode step of 64 rows, int8 weights. A head of 64 under 8 KV heads is a
    shape neither paged kernel had met on the chip. A pass says the chip's
    compiler takes both step programs with ONE instance of the paged kernel for
    the six attention layers, the expert kernels at this shape (gate and up in
    column blocks), no copy of a pool (the pools never pass through a ``cond``:
    where they did, the chip's compiler copied 0.8 GB a side an attention layer
    once the steps ran in the chunk's loop) and no int8 result that is not a
    parameter (a copy of a weight)."""
    import re

    from inference_gateway_tpu.models import mixtral
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    cfg = mixtral.PRESETS["lfm2-8b-a1b"]
    slots, page, seq, T = 64, 128, 2048, 576
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=SingleDeviceSharding(topo.devices[0])), tree)
    params = place(jax.eval_shape(lambda: init_quantized_llama_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: mixtral.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq))))
    assert cache["k"].shape == (6, slots * 16, page, 512) and cache["conv"].shape == (18, slots, 2, 2048)
    sds = lambda shape: place(jax.ShapeDtypeStruct(shape, I32))
    row, table = sds((slots,)), sds((slots, seq // page))
    if form == "mixed":
        fn = lambda p, c, tok, pos, w, table, qs, ql, kl: mixtral.forward_ragged(
            p, cfg, tok, pos, c, w, table, qs, ql, kl, with_aux=True)
        args = (params, cache, sds((1, T)), sds((1, T)), sds((1, T)), table, row, row, row)
    else:
        # As the engine's chunk runs it: the steps in a ``while_loop`` with the
        # cache in its carry (that is where the first tree's copy appeared).
        def fn(p, c, tok, pos, table, done):
            def step(carry):
                i, c, tok, pos = carry
                w = jnp.take_along_axis(table, (pos // page)[:, None], axis=1)[:, 0] * page + pos % page
                logits, c, _ = mixtral.forward_paged(p, cfg, tok[:, None], pos[:, None], jnp.where(done, 0, pos + 1),
                                                     c, w[:, None], table, mode="decode", valid=~done, with_aux=True)
                return i + 1, c, jnp.argmax(logits, -1).astype(I32), pos + 1

            return jax.lax.while_loop(lambda carry: carry[0] < 8, step, (jnp.int32(0), c, tok, pos))[1:]

        args = (params, cache, row, row, table, place(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call"', text)
    assert len(kernels) >= 2                       # the paged kernel and the expert kernels
    paged = "_ragged_paged_attn_kernel" if form == "mixed" else "paged_attention"
    assert len(set(re.findall(rf"%\S*{paged}\S* = ", text))) <= 2, "more than one instance of the paged kernel"
    pool = 2 * 6 * slots * 16 * page * 512 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool // 5
    # (The temporaries do not show a copy that lands in the carry's own buffer: look for the instruction.)
    assert not re.findall(rf"= bf16\[{6 * slots * 16 * page},512\]\S* copy\(", text), "a pool is copied"
    # A weight's slice may be a producer INSIDE the matmul's fusion, or be fetched ahead into the fast memory
    # (a result in ``S(1)``), and a stack may be laid out anew ONCE a call (an entry parameter's copy: 25 MB
    # of q projections a chunk of 8 steps); any other int8 result is a copy in HBM a layer.
    computation, copies = "", []
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{", line)
        computation = opened.group(1) if opened else computation
        made = re.match(r"\s*(?:ROOT )?%\S+ = s8\[[\d,]+\](\{\S*\})? (fusion|copy|dynamic-slice)\((%\S+)", line)
        if (made and not computation.startswith("fused_computation") and "S(1)" not in (made.group(1) or "")
                and not made.group(3).startswith("%p__")):
            copies.append(line.strip()[:160])
    assert not copies, copies[:3]


def _weight_copies(text: str) -> list:
    """The int8 results of a compiled program that are copies of a weight in
    HBM. A weight's slice may be a producer INSIDE the matmul's fusion, be
    fetched ahead into the fast memory (a result in ``S(1)``), or be an entry
    parameter laid out anew ONCE a call, directly or through its prefetch (a
    copy-done of a parameter's copy-start); any other is a copy a layer."""
    import re

    prefetched = set(re.findall(r"(%copy-start\S*) = .* copy-start\(%p__", text))
    of_parameter = {done for done, start in re.findall(r"(%copy-done\S*) = .* copy-done\((%copy-start[\w.\-]*)\)", text)
                    if start in prefetched}
    computation, copies = "", []
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{", line)
        computation = opened.group(1) if opened else computation
        made = re.match(r"\s*(?:ROOT )?%\S+ = s8\[[\d,]+\](\{\S*\})? (fusion|copy|dynamic-slice)\((%[\w.\-]+)", line)
        if (made and not computation.startswith("fused_computation") and "S(1)" not in (made.group(1) or "")
                and not made.group(3).startswith("%p__") and made.group(3) not in of_parameter):
            copies.append(line.strip()[:160])
    return copies


@pytest.mark.parametrize("form", ["mixed", "decode"])
def test_xing4_step_programs_compile_for_v5e_at_the_published_widths(topo, monkeypatch, as_on_tpu, form):
    """Xing4.0-29B-A4B's first stage (ISSUE 47: 10 layers, a latent attention of 32
    heads on rows of 576 values in 640 lanes, four residual streams, 64 experts of
    1,024 top-4 beside a shared one) as its cell serves it: 32 slots of 100 pages
    of 128, a mixed step of 544 packed tokens and a decode step of 32 rows, int8
    weights. A pass says the chip's compiler takes both step programs: the latent
    kernel under both of its names (a DMA may not slice inside a lane tile, which
    is why the pool's row is 640 wide), the Sinkhorn kernel, the expert kernels;
    the ONE pool array is updated in place (no copy of it, and the temporaries
    stay far under it: the streams are kept out of every branch) and no int8
    result is made that is not a parameter (a copy of a weight)."""
    import re

    from inference_gateway_tpu.models import mixtral
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    monkeypatch.setattr("inference_gateway_tpu.ops.mla_attention.device_platform", lambda: "tpu")
    cfg = mixtral.PRESETS["xing4.0-stage10"]
    slots, page, seq, T = 32, 128, 12800, 544
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=SingleDeviceSharding(topo.devices[0])), tree)
    params = place(jax.eval_shape(lambda: init_quantized_llama_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: mixtral.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq))))
    assert {n: a.shape for n, a in cache.items()} == {"kv": (10, slots * 100, page, 640)}
    sds = lambda shape: place(jax.ShapeDtypeStruct(shape, I32))
    row, table = sds((slots,)), sds((slots, seq // page))
    if form == "mixed":
        fn = lambda p, c, tok, pos, w, table, qs, ql, kl: mixtral.forward_ragged(
            p, cfg, tok, pos, c, w, table, qs, ql, kl, with_aux=True)
        args = (params, cache, sds((1, T)), sds((1, T)), sds((1, T)), table, row, row, row)
        kernels = ("mla_decode_attn_kernel", "mla_chunk_attn_kernel", "mhc_sinkhorn_kernel")
    else:
        def fn(p, c, tok, pos, table, done):   # as the engine's chunk runs it: the cache in a while_loop's carry
            def step(carry):
                i, c, tok, pos = carry
                w = jnp.take_along_axis(table, (pos // page)[:, None], axis=1)[:, 0] * page + pos % page
                logits, c, _ = mixtral.forward_paged(p, cfg, tok[:, None], pos[:, None], jnp.where(done, 0, pos + 1),
                                                     c, w[:, None], table, mode="decode", valid=~done, with_aux=True)
                return i + 1, c, jnp.argmax(logits, -1).astype(I32), pos + 1

            return jax.lax.while_loop(lambda carry: carry[0] < 8, step, (jnp.int32(0), c, tok, pos))[1:]

        args = (params, cache, row, row, table, place(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
        kernels = ("mla_decode_attn_kernel", "mhc_sinkhorn_kernel")
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    for name in kernels + ("moe_gate_up_gmm", "moe_down_gmm"):
        assert re.search(rf"%\S*{name}\S* = ", text), name
    pool = 10 * slots * 100 * page * 640 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool // 20
    assert not re.findall(rf"= bf16\[{10 * slots * 100 * page},640\]\S* copy\(", text), "the pool is copied"
    # (As LFM2's test reads it; here an entry parameter laid out anew once a call may come through its
    # prefetch: the 576-column W_DKV stack, 16.5 MB a chunk of 8 steps, is a copy-done of a parameter's copy-start.)
    assert not _weight_copies(text), _weight_copies(text)[:3]


@pytest.mark.parametrize("form", ["mixed", "decode"])
def test_granite4_step_programs_compile_for_v5e_at_the_published_widths(topo, monkeypatch, as_on_tpu, form):
    """granite-4.0-h-small's first stage (ISSUE 50: 10 layers, nine Mamba-2 mixers
    of 128 heads of 64 with a state of 128 beside one NoPE GQA layer of 32 / 8
    heads of 128, 72 experts of 768 top-10 and a shared MLP of 1,536 in every
    layer) as its cell serves it: 32 slots of 100 pages of 128, a mixed step of
    544 packed tokens and a decode step of 32 rows, int8 weights. A pass says the
    chip's compiler takes both step programs: the blocked scan's kernel
    (``ssd_chunk_kernel``: a head's rows a sublane slice, the store read and
    written by DMA at a dynamic layer and slot) in the mixed step's two runs of
    Mamba layers and in no decode step; the one-token update's kernel
    (``ssm_state_update_kernel``: a walk of the rows that advance, a megabyte of
    a row's states a visit by DMA, ISSUE 63) in both programs,
    and no fusion that touches the store;
    the expert kernels at 72 experts of 768; and holds NO copy of the 1.2 GB state
    store (the runs of one kind are scanned apart, so no ``cond`` stands around
    it: each layer's update lands in the carried buffer) nor of the pool, the
    temporaries far under the store, and no int8 result that is not a parameter
    (a copy of a weight: the shared MLP's matrices are read at the layer's index,
    not sliced by a run's scan)."""
    import re

    from inference_gateway_tpu.models import mixtral
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    cfg = mixtral.PRESETS["granite-4.0-h-small-stage10"]
    slots, page, seq, T = 32, 128, 12800, 544
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=SingleDeviceSharding(topo.devices[0])), tree)
    params = place(jax.eval_shape(lambda: init_quantized_llama_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: mixtral.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq))))
    assert {n: a.shape for n, a in cache.items()} == {
        "k": (1, slots * 100, page, 1024), "v": (1, slots * 100, page, 1024),
        "conv": (9, slots, 3, 8448), "ssm": (9, slots, 128, 64, 128)}
    store = 9 * slots * 128 * 64 * 128 * 4
    assert store + 9 * slots * 3 * 8448 * 4 == slots * cfg.state_bytes_per_slot == 1_237_155_840
    sds = lambda shape: place(jax.ShapeDtypeStruct(shape, I32))
    row, table = sds((slots,)), sds((slots, seq // page))
    if form == "mixed":
        fn = lambda p, c, tok, pos, w, table, qs, ql, kl: mixtral.forward_ragged(
            p, cfg, tok, pos, c, w, table, qs, ql, kl, with_aux=True)
        args = (params, cache, sds((1, T)), sds((1, T)), sds((1, T)), table, row, row, row)
    else:
        def fn(p, c, tok, pos, table, done):   # as the engine's chunk runs it: the cache in a while_loop's carry
            def step(carry):
                i, c, tok, pos = carry
                w = jnp.take_along_axis(table, (pos // page)[:, None], axis=1)[:, 0] * page + pos % page
                logits, c, _ = mixtral.forward_paged(p, cfg, tok[:, None], pos[:, None], jnp.where(done, 0, pos + 1),
                                                     c, w[:, None], table, mode="decode", valid=~done, with_aux=True)
                return i + 1, c, jnp.argmax(logits, -1).astype(I32), pos + 1

            return jax.lax.while_loop(lambda carry: carry[0] < 8, step, (jnp.int32(0), c, tok, pos))[1:]

        args = (params, cache, row, row, table, place(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    for name in ("moe_gate_up_gmm", "moe_down_gmm"):
        assert re.search(rf"%\S*{name}\S* = ", text), name
    scans = set(re.findall(r"%ssd_chunk_kernel[\w.\-]* = ", text))
    assert len(scans) == (2 if form == "mixed" else 0), scans          # one a run of Mamba layers; none in a decode step
    assert len(set(re.findall(r"%ssm_state_update_kernel[\w.\-]* = ", text))) == 2    # the one-token rows, both programs
    assert not re.findall(r"%[\w.\-]+ = f32\[(?:9,)?32,128,64,128\]\S* fusion\(", text), "a fusion reads or writes the store"
    assert compiled.memory_analysis().temp_size_in_bytes < store // 4
    assert not re.findall(r"= f32\[9,32,128,64,128\]\S* copy\(", text), "the state store is copied"
    assert not re.findall(r"= f32\[32,128,64,128\]\S* copy\(", text), "a layer's states are copied"
    assert not re.findall(rf"= bf16\[{slots * 100 * page},1024\]\S* copy\(", text), "the pool is copied"
    # No loop over one state a token: the mixed step holds no f32[64,128] or f32[128,64,128] result in a while body
    assert not re.findall(r"= f32\[(?:128,)?64,128\]\{", text), "a state a token: a loop over tokens"
    assert not _weight_copies(text), _weight_copies(text)[:3]


@pytest.mark.parametrize("form", ["mixed", "decode"])
def test_kimi_linear_step_programs_compile_for_v5e_at_the_published_widths(topo, monkeypatch, as_on_tpu, form):
    """Kimi-Linear-48B-A3B's first stage as ONE of its two chips holds it (ISSUE 54:
    8 layers, six Kimi Delta Attention layers of 32 heads of 128 beside two latent
    attention layers of 32 heads over a latent of 512 + 64, a dense layer in front,
    128 of 256 experts of 1,024 top-8 under the whole router and a shared expert)
    as its cell serves it: 32 slots of 100 pages of 128, a mixed step of 544 packed
    tokens and a decode step of 32 rows, int8 weights. A pass says the chip's
    compiler takes both step programs: the chunk kernel (``kda_chunk_kernel``:
    tokens in the sublanes, a head's 128 key channels the lanes, L by sub-blocks,
    the solve by products, the store read and written by DMA at a dynamic layer and
    slot) in the mixed step's three runs of KDA layers and in no decode step; the
    one-token update's kernel (``kda_state_update_kernel``) in both; the absorbed
    latent read's kernels over a pool of TWO layers; the expert kernels over 128
    held experts; NO copy of the 0.4 GB store nor of the pool, no fusion that
    touches the store, and no int8 result that is not a parameter."""
    import re

    from inference_gateway_tpu.models import mixtral
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    monkeypatch.setattr("inference_gateway_tpu.ops.mla_attention.device_platform", lambda: "tpu")
    cfg = mixtral.PRESETS["kimi-linear-48b-a3b-stage8"]
    slots, page, seq, T = 32, 128, 12800, 544
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=SingleDeviceSharding(topo.devices[0])), tree)
    params = place(jax.eval_shape(lambda: init_quantized_llama_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: mixtral.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq))))
    assert {n: a.shape for n, a in cache.items()} == {
        "kv": (2, slots * 100, page, 640), "conv": (6, slots, 3, 12288), "kda": (6, slots, 32, 128, 128)}
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert nbytes(params) == 7_909_210_880 and nbytes(cache) == 1_048_576_000 + 430_964_736
    assert slots * cfg.state_bytes_per_slot == 430_964_736
    assert params["layers"]["router"].shape == (7, 2304, 256) and params["layers"]["wg"].q.shape == (7, 128, 2304, 1024)
    sds = lambda shape: place(jax.ShapeDtypeStruct(shape, I32))
    row, table = sds((slots,)), sds((slots, seq // page))
    if form == "mixed":
        fn = lambda p, c, tok, pos, w, table, qs, ql, kl: mixtral.forward_ragged(
            p, cfg, tok, pos, c, w, table, qs, ql, kl, with_aux=True)
        args = (params, cache, sds((1, T)), sds((1, T)), sds((1, T)), table, row, row, row)
    else:
        def fn(p, c, tok, pos, table, done):   # as the engine's chunk runs it: the cache in a while_loop's carry
            def step(carry):
                i, c, tok, pos = carry
                w = jnp.take_along_axis(table, (pos // page)[:, None], axis=1)[:, 0] * page + pos % page
                logits, c, _ = mixtral.forward_paged(p, cfg, tok[:, None], pos[:, None], jnp.where(done, 0, pos + 1),
                                                     c, w[:, None], table, mode="decode", valid=~done, with_aux=True)
                return i + 1, c, jnp.argmax(logits, -1).astype(I32), pos + 1

            return jax.lax.while_loop(lambda carry: carry[0] < 8, step, (jnp.int32(0), c, tok, pos))[1:]

        args = (params, cache, row, row, table, place(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    for name in ("moe_gate_up_gmm", "moe_down_gmm", "mla_"):
        assert re.search(rf"%\S*{name}\S* = ", text), name
    chunks = set(re.findall(r"%kda_chunk_kernel[\w.\-]* = ", text))
    assert len(chunks) == (3 if form == "mixed" else 0), chunks           # one a run of KDA layers; none in a decode step
    assert len(set(re.findall(r"%kda_state_update_kernel[\w.\-]* = ", text))) == 3   # the one-token rows, both programs
    assert not re.findall(r"%[\w.\-]+ = f32\[(?:6,)?32,(?:32,128|4096),128\]\S* fusion\(", text), "a fusion reads or writes the store"
    store = 6 * slots * 32 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < store // 2
    assert not re.findall(r"= f32\[(?:6,)?32,(?:32,128|4096),128\]\S* copy\(", text), "the state store is copied"
    assert not re.findall(rf"= bf16\[{2 * slots * 100 * page},640\]\S* copy\(", text), "the pool is copied"
    # (W_DKV's 576 columns are not whole lane tiles: a latent layer's 1.3 MB slice of it is laid out anew where a run of
    # ONE layer reads it, as Xing's stack is through its prefetch; every other int8 result is a parameter.)
    # Since ISSUE 59 the absorbed products read W_UKV as int8, by head: a latent layer's 4.2 MB is laid out anew ONCE
    # a call, in the entry computation (the parent converted it to bf16 a step and laid THAT out).
    entry = text[text.index("\nENTRY "):]
    copies = [c for c in _weight_copies(text) if "s8[1,2304,576]" not in c
              and not ("s8[1,512,8192]" in c and f" {c.split(' = ')[0]} = " in entry)]
    assert not copies, copies[:3]
    assert not re.findall(r"= (?:bf16|f32)\[(?:1,)?512,(?:8192|32,256)\]\S* [^\n]*mla\.absorb", text)   # no dequantised W_UKV


@pytest.mark.parametrize("form", ["mixed", "decode"])
def test_openpangu_step_programs_compile_for_v5e_at_the_published_widths(topo, monkeypatch, as_on_tpu, form):
    """openPangu-Ultra-MoE-718B as ONE of the sixteen chips that share each layer
    (ISSUE 56: layer 0, six expert layers and the next-token module; a latent
    attention of 128 heads on rows of 576 values in 640 lanes under a query
    bottleneck of 1,536; 16 of 256 experts of 2,048 top-8 under the whole router;
    19,200 of 153,600 rows of the vocabulary) as its cell serves it: 64 slots of
    16 pages of 128, the ENGINE's two step programs in their real nesting (the
    packed buffer's unpacking, the carried draft state, the round's acceptance,
    the chunk's while_loop of 8 rounds), int8 weights. A pass says the chip's
    compiler takes both: a decoding row's two queries through
    ``mla_pair_attn_kernel`` (once in the dense layer's group, once in the
    expert layers' scan, once in the module), beside it in a mixed step the
    chunk's tiles; the expert kernels; ONE pool of eight planes updated in place
    (no copy of it a round, temporaries far under it); no int8 result that is not
    a parameter; and ONE program each, whatever was accepted: acceptance is data
    (positions, lengths and write indices are operands), so nothing in the
    signature depends on it."""
    import re

    import inference_gateway_tpu.serving.engine as engine_module
    from inference_gateway_tpu.models import mixtral
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.engine import STOP_TABLE_WIDTH, Engine, EngineConfig
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    monkeypatch.setattr("inference_gateway_tpu.ops.mla_attention.device_platform", lambda: "tpu")
    preset = "openpangu-ultra-moe-718b-stage7"
    cfg = mixtral.PRESETS[preset]
    slots, page, seq = 64, 128, 2048
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=SingleDeviceSharding(topo.devices[0])), tree)
    params = jax.eval_shape(lambda: init_quantized_llama_params(jax.random.PRNGKey(0), cfg))
    # An engine of the cell's shapes around SHAPES: the tree handed in as structs, a pool of one page while it
    # is built (the programs take the real pool's struct), the host's account of its device left as it is.
    real_pool = mixtral.init_paged_cache
    monkeypatch.setattr(mixtral, "init_paged_cache", lambda c, pc, dtype=BF16: {
        "kv": jnp.zeros((c.cache_layers, 1, pc.page_size, c.latent_row), dtype)})
    monkeypatch.setattr(engine_module, "device_info", lambda: {"platform": "cpu", "device_kind": "cpu", "device_count": 1})
    engine = Engine(EngineConfig(model=preset, attention="paged", page_size=page, max_slots=slots, max_seq_len=seq,
                                 use_mesh=False, decode_chunk=8), params=params)
    assert engine.mtp and engine.mixed_budget == 576 and engine.round_tokens == 2
    cache = place(jax.eval_shape(lambda: real_pool(cfg, PagedCacheConfig(page, 0, slots, seq))))
    assert {n: a.shape for n, a in cache.items()} == {"kv": (8, slots * 16, page, 640)}
    params = place(params)
    sds = lambda shape, dtype=I32: place(jax.ShapeDtypeStruct(shape, dtype))
    like = lambda a: sds(a.shape, a.dtype)
    mtp = place(jax.eval_shape(lambda: engine._mtp))
    _, mnext, mbits, mterm, mbias = engine._mask_args_ee()
    if form == "mixed":
        lowered = Engine._mixed_step_fn.lower(
            engine, params, cache, sds((engine._mixed_pack.size,)), like(engine._rng), like(engine._last_block),
            mnext=like(mnext), mbits=like(mbits), mbias=like(mbias), automaton=False, mtp=mtp)
        kernels = {"mla_pair_attn_kernel": 3, "mla_chunk_attn_kernel": 3, "mla_decode_attn_kernel": 0}
    else:
        row, real, flag = sds((slots,)), sds((slots,), jnp.float32), sds((slots,), jnp.bool_)
        lowered = Engine._decode_chunk_fn_paged_ee.lower(
            engine, params, cache, row, row, flag, row, sds((slots, STOP_TABLE_WIDTH)), sds((slots, seq // page)), row,
            real, real, row, flag, like(engine._rng), mstates=row, mnext=like(mnext), mbits=like(mbits),
            mterm=like(mterm), mbias=like(mbias), n_steps=8, automaton=False, mtp=mtp)
        kernels = {"mla_pair_attn_kernel": 3, "mla_chunk_attn_kernel": 0, "mla_decode_attn_kernel": 0}
    compiled = lowered.compile()
    text = compiled.as_text()
    for name, count in kernels.items():
        assert len(set(re.findall(rf"%\S*{name}[\w.\-]* = ", text))) == count, name
    for name in ("moe_gate_up_gmm", "moe_down_gmm"):
        assert re.search(rf"%\S*{name}\S* = ", text), name
    pool = 8 * slots * 16 * page * 640 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool // 3
    assert not re.findall(rf"= bf16\[(?:{8 * slots * 16 * page}|8,{slots * 16},{page}),640\]\S* copy\(", text), "the pool is copied"
    # No int8 result that is not a parameter INSIDE a loop (a copy a layer or a round). What ``_weight_copies``
    # finds here (it knows a parameter as ``p__``; the engine's programs name theirs ``params__...``) are entry
    # parameters laid out anew ONCE a call, in the entry computation: W_DKV's stacks (576 columns are not whole
    # lane tiles) and W_UKV's (read by head in the absorbed form), under 150 MB in all: a fiftieth of a round's bytes
    # over a chunk of eight.
    # A mixed step visits a layer ONCE, so there W_UKV's relayout by head (int8, 16.8 MB: ISSUE 59) stands in
    # the layer scan, a layer's at a time; a chunk's eight rounds find the stack laid out once, in the entry.
    entry = text[text.index("\nENTRY "):]
    copies = _weight_copies(text)
    in_a_loop = [c for c in copies if f" {c.split(' = ')[0]} = " not in entry]
    assert all(form == "mixed" and "= s8[1,512,32768]" in c for c in in_a_loop), ("a weight laid out anew inside a loop", in_a_loop[:3])
    moved = sum(int(np.prod([int(d) for d in re.match(r"%\S+ = s8\[([\d,]+)\]", c).group(1).split(",")])) for c in copies)
    assert moved < 150e6, (moved, copies[:3])
    # W_UKV is multiplied as it is stored (ISSUE 59): no bf16 or float32 result of the matrix's size (512 x 128 heads
    # of 256) under ``mla.absorb``, inside a fusion or out of it; the parent held sixteen in its decode program.
    assert not re.findall(r"= (?:bf16|f32)\[(?:1,)?512,(?:32768|128,256)\]\S* [^\n]*mla\.absorb", text)
    # No selection sorts the vocabulary's slice (ISSUE 57): the verify queries' (64, 2, 19200) were a sort of every
    # column, 1.8 ms a round; the sampler's groups of 32 columns leave sorts of 600 maxima and 2,048 candidates.
    sorts = re.findall(r"= \(?f32\[([\d,]+)\][^\n]* sort\(", text)
    assert sorts and all(int(shape.split(",")[-1]) <= 2048 for shape in sorts), sorts


@pytest.mark.parametrize("form", ["mixed", "decode"])
def test_evabyte_step_programs_compile_for_v5e_at_the_published_widths(topo, monkeypatch, as_on_tpu, form):
    """EvaByte-6.5B whole on one chip (ISSUE 61: 32 layers of 4,096 with 32 KV heads
    of 128 and NO grouping, EVA attention over windows of 2,048 and chunks of 16, a
    vocabulary of 320) as its cell serves it: 5 slots of 21 pages of 128 where full
    attention would hold 100, the ENGINE's two step programs in their real nesting
    (the packed buffer's unpacking, the chunk's while_loop of 8 steps, the rows'
    cached lengths derived on the device), int8 weights. A pass says the chip's
    compiler takes both: the paged kernels at 32 KV heads over a page table of 21
    columns, the fold in front of the layers (a loop over the rows that start a
    window around a loop over the layers), 13.5 GB of arguments with temporaries
    far under the pool, the pool updated in place, and no weight copied a layer.
    (The first tree's fold gathered a window's 16 pages by index: the compiler laid
    half the pool out anew for it, 1.6 GB twice, and the mixed step did not fit the
    chip. It slices the pages where they lie.)"""
    import re

    import inference_gateway_tpu.serving.engine as engine_module
    from inference_gateway_tpu.models import llama
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.engine import STOP_TABLE_WIDTH, Engine, EngineConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    preset = "evabyte-6.5b"
    cfg = llama.PRESETS[preset]
    slots, page, seq = 5, 128, 12800
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=SingleDeviceSharding(topo.devices[0])), tree)
    params = jax.eval_shape(lambda: init_quantized_llama_params(jax.random.PRNGKey(0), cfg))
    real_pool = llama.init_paged_cache
    monkeypatch.setattr(llama, "init_paged_cache", lambda c, pc, dtype=BF16: {
        n: jnp.zeros((c.num_layers, 1, pc.page_size, c.num_kv_heads * c.hd), dtype) for n in "kv"})
    monkeypatch.setattr(engine_module, "device_info", lambda: {"platform": "cpu", "device_kind": "cpu", "device_count": 1})
    engine = Engine(EngineConfig(model=preset, attention="paged", page_size=page, max_slots=slots, max_seq_len=seq,
                                 use_mesh=False, decode_chunk=8), params=params)
    pages = engine.page_cfg.max_pages_per_slot
    assert (pages, engine.page_cfg.resolve_num_pages(), engine.mixed_budget) == (21, 105, 517)
    assert engine.sampler_plan["path"] == "direct"          # 320 columns: 2.5 lane groups
    cache = place(jax.eval_shape(lambda: real_pool(cfg, engine.page_cfg)))
    assert {n: a.shape for n, a in cache.items()} == {n: (32, 105, page, 4096) for n in "kv"}
    params = place(params)
    sds = lambda shape, dtype=I32: place(jax.ShapeDtypeStruct(shape, dtype))
    like = lambda a: sds(a.shape, a.dtype)
    _, mnext, mbits, mterm, mbias = engine._mask_args_ee()
    if form == "mixed":
        lowered = Engine._mixed_step_fn.lower(
            engine, params, cache, sds((engine._mixed_pack.size,)), like(engine._rng), like(engine._last_block),
            mnext=like(mnext), mbits=like(mbits), mbias=like(mbias), automaton=False)
        kernels = {"ragged_paged_attention_tpu": 1, "paged_attention_tpu": 1, "pool_write_kernel": 1}   # (ISSUE 62: 517 rows)
    else:
        row, real, flag = sds((slots,)), sds((slots,), jnp.float32), sds((slots,), jnp.bool_)
        lowered = Engine._decode_chunk_fn_paged_ee.lower(
            engine, params, cache, row, row, flag, row, sds((slots, STOP_TABLE_WIDTH)), sds((slots, pages)), row,
            real, real, row, flag, like(engine._rng), mstates=row, mnext=like(mnext), mbits=like(mbits),
            mterm=like(mterm), mbias=like(mbias), n_steps=8, automaton=False)
        kernels = {"ragged_paged_attention_tpu": 0, "paged_attention_tpu": 1, "pool_write_kernel": 0}   # (5 rows: the scatter)
    compiled = lowered.compile()
    text = compiled.as_text()
    for name, count in kernels.items():
        assert len(set(re.findall(rf"%{name}[\w.\-]* = ", text))) == count, name
    pool = 2 * 32 * 105 * page * 4096 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool and memory.temp_size_in_bytes < pool // 20
    assert not re.findall(rf"= bf16\[(?:{32 * 105 * page}|32,105,{page}),4096\]\S* copy\(", text), "the pool is copied"
    assert re.search(r"kv\.write/while/body/while/body", text), "the fold stands in front of the layers"
    assert not [c for c in _weight_copies(text) if "params__" not in c], "a weight is copied"


# ISSUE 63: the one-token state updates alone, as the two cells' step programs hand them over (Granite's and
# Kimi's stores at 32 slots) and at ROADMAP W9's 64 slots, which no cell runs yet: a visit of a megabyte by the
# store's shape (32 of 128 heads of 64 x 128; 16 of 32 heads of 128 x 128), three of them in VMEM beside the
# slots' row operands, the store in HBM and aliased through the call. The ceiling is the traced equations of a
# kernel's body (122 and 126 as written, 111 and 153 before: the operands' copies, the walk's loop, one tile's or one head's
# arithmetic; counts are divided and taken modulo by ``lax.div`` / ``lax.rem``, one equation where ``//`` is a dozen).
STATE_UPDATES = {"granite": ("ssd", (9, 32, 128, 64, 128)), "granite_64_slots": ("ssd", (9, 64, 128, 64, 128)),
                 "kimi": ("kda", (6, 32, 32, 128, 128)), "kimi_64_slots": ("kda", (6, 64, 32, 128, 128))}
STATE_UPDATE_EQUATIONS = 130


def _state_update(sds, which, shape):
    from inference_gateway_tpu.ops import kda, ssd

    L, S, Hh, R, N = shape
    f32 = lambda *dims: sds(dims, jnp.float32)
    flags = (sds((S,), jnp.bool_), sds((S,), jnp.bool_))
    if which == "ssd":
        return ssd.state_update_tpu, (f32(*shape), sds((), I32), f32(S, Hh), f32(S, Hh, R), f32(S, N), f32(S, N),
                                      f32(Hh), f32(Hh), *flags)
    return kda.state_update_tpu, (f32(*shape), sds((), I32), *[f32(S, Hh, R)] * 4, f32(S, Hh), *flags)


@pytest.mark.parametrize("case", list(STATE_UPDATES))
def test_state_update_kernels_compile_for_v5e_with_no_copy_of_the_store(one_chip, case):
    """One kernel a launch under the name the benchmark's readers find it by, the
    store aliased through it: no copy of the store and no temporary of its size."""
    which, shape = STATE_UPDATES[case]
    fn, shapes = _state_update(one_chip, which, shape)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(rf"%{'ssm' if which == 'ssd' else 'kda'}_state_update_kernel[\w.\-]* = ", text)
    L, S, Hh, R, N = shape
    assert not re.findall(rf"= f32\[{L},{S},(?:{Hh},{R}|{Hh * R}),{N}\]\S* (?:copy|fusion)\(", text), "the store is copied"
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("case", list(STATE_UPDATES))
def test_state_update_kernels_stay_inside_their_start_up_budget(case):
    """No clock: the traced equations of the kernel's body and of what stands around it."""
    which, shape = STATE_UPDATES[case]
    fn, shapes = _state_update(jax.ShapeDtypeStruct, which, shape)
    jaxpr = jax.make_jaxpr(fn)(*shapes).jaxpr
    (body,) = _kernel_bodies(jaxpr, ("ssm" if which == "ssd" else "kda") + "_state_update_kernel")
    assert _equations(body) <= STATE_UPDATE_EQUATIONS
    assert _equations(jaxpr) - _equations(body) <= 40


@pytest.mark.parametrize("form", ["mixed", "decode"])
def test_jamba2_step_programs_compile_for_v5e_at_the_published_widths(topo, monkeypatch, as_on_tpu, form):
    """AI21-Jamba2-3B whole (ISSUE 64: 28 layers, 26 Mamba-1 mixers of 5,120
    channels x 16 beside two NoPE layers of 20 query heads over ONE KV head, a dense
    MLP of 8,192 in every layer, a 65,536-row tied head) as its cell serves it: 32
    slots of 100 pages of 128, a mixed step of 544 packed tokens and a decode step
    of 32 rows, int8 weights. A pass says the chip's compiler takes both step
    programs: the chunk kernel in the mixed step's three runs of Mamba layers and
    in no decode step, NO loop over one state a token, NO copy of the 0.3 GB store
    nor of the pool, and no int8 result that is not a parameter (the MLP's
    matrices are read at the layer's index, not sliced by a run's scan)."""
    from inference_gateway_tpu.models import jamba
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    monkeypatch.setattr("inference_gateway_tpu.ops.paged_attention.device_platform", lambda: "tpu")
    cfg = jamba.PRESETS["jamba2-3b"]
    slots, page, seq, T = 32, 128, 12800, 544
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=SingleDeviceSharding(topo.devices[0])), tree)
    params = place(jax.eval_shape(lambda: init_quantized_llama_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: jamba.init_paged_cache(cfg, PagedCacheConfig(page, 0, slots, seq))))
    assert {n: a.shape for n, a in cache.items()} == {
        "k": (2, slots * 100, page, 128), "v": (2, slots * 100, page, 128),
        "conv": (26, slots, 3, 5120), "ssm": (26, slots, 16, 5120)}
    assert sum(a.size * a.dtype.itemsize for a in (cache["ssm"], cache["conv"])) == slots * cfg.state_bytes_per_slot == 323_747_840
    sds = lambda shape: place(jax.ShapeDtypeStruct(shape, I32))
    row, table = sds((slots,)), sds((slots, seq // page))
    if form == "mixed":
        fn = lambda p, c, tok, pos, w, table, qs, ql, kl: jamba.forward_ragged(
            p, cfg, tok, pos, c, w, table, qs, ql, kl, with_aux=True)
        args = (params, cache, sds((1, T)), sds((1, T)), sds((1, T)), table, row, row, row)
    else:
        def fn(p, c, tok, pos, table, done):   # as the engine's chunk runs it: the cache in a while_loop's carry
            def step(carry):
                i, c, tok, pos = carry
                w = jnp.take_along_axis(table, (pos // page)[:, None], axis=1)[:, 0] * page + pos % page
                logits, c, _ = jamba.forward_paged(p, cfg, tok[:, None], pos[:, None], jnp.where(done, 0, pos + 1),
                                                   c, w[:, None], table, mode="decode", valid=~done, with_aux=True)
                return i + 1, c, jnp.argmax(logits, -1).astype(I32), pos + 1

            return jax.lax.while_loop(lambda carry: carry[0] < 8, step, (jnp.int32(0), c, tok, pos))[1:]

        args = (params, cache, row, row, table, place(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    scans = set(re.findall(r"%ssm_scan_chunk_kernel[\w.\-]* = ", text))
    assert len(scans) == (3 if form == "mixed" else 0), scans          # one a run of Mamba layers; none in a decode step
    assert len(set(re.findall(r"%ssm_scan_decode_kernel[\w.\-]* = ", text))) == 3   # the one-token rows, in both programs
    store = 26 * slots * 16 * 5120 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < store
    assert not re.findall(r"= f32\[26,32,16,5120\]\S* copy\(", text), "the state store is copied"
    assert not re.findall(rf"= bf16\[{2 * slots * 100 * page},128\]\S* copy\(", text), "the pool is copied"
    # No loop over one state a token: no result of a state's shape is written into an entry of the store in a while body
    assert not re.findall(r"= f32\[32,16,5120\]\S* dynamic-update-slice\(", text), "a state a token: a loop over tokens"
    assert not _weight_copies(text), _weight_copies(text)[:3]
