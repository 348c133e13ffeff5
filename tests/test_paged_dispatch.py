"""Paged-attention dispatch audit (round-4 verdict next #10).

The GSPMD gather fallback measured ~10.6× slower than the Pallas kernel
at serving shape on a live v5e (2026-07-29, TinyLlama: 25,856 µs vs
2,448 µs). These tests make the dispatch an assertion, not
an accident: every committed serving profile's layout must land on a
kernel path on TPU, and the decision function must agree with the live
dispatcher's observable behavior.
"""

import numpy as np
import pytest

from inference_gateway_tpu.ops.paged_attention import paged_dispatch
from inference_gateway_tpu.serving.profiles import PROFILES, resolve_model_cfg


@pytest.mark.parametrize("name", [n for n, p in PROFILES.items() if p.attention == "paged"])
def test_committed_profiles_dispatch_to_kernel(name):
    """No committed profile may silently serve at 10.6× the attention
    cost. If a profile legitimately needs the gather path one day, it
    must say so here explicitly."""
    p = PROFILES[name]
    cfg = resolve_model_cfg(p.model)
    tp = p.mesh.get("tp", 1)
    path, reason = paged_dispatch(
        num_kv_heads=cfg.num_kv_heads,
        num_q_heads=cfg.num_heads,
        folded_dim=cfg.num_kv_heads * cfg.hd,
        tp=tp,
        platform="tpu",
        n_devices=p.n_chips,
    )
    if tp > 1:
        assert path == "kernel_sharded", (name, path, reason)
    elif p.n_chips == 1:
        assert path == "kernel", (name, path, reason)
    else:
        # tp=1 multi-chip paged profiles would gather — none may exist
        # without an explicit exemption recorded here.
        pytest.fail(f"{name}: tp=1 multi-chip paged layout hits the "
                    f"gather fallback ({reason}); add tp or an exemption")


def test_gather_matrix_closed_every_tpu_layout_takes_a_kernel():
    """ISSUE 12: the old fallback matrix (misaligned folded axis,
    non-divisible heads, tp=1 multi-device) now dispatches to a kernel
    path; gather remains ONLY for non-TPU platforms and the explicit
    kill switch."""
    # tinyllama-like: Hkv*D = 256, aligned → kernel single-chip.
    assert paged_dispatch(4, 32, 256)[0] == "kernel"
    # Misaligned folded axis (Hkv*D = 192): lane-padded scratch.
    path, reason = paged_dispatch(3, 24, 192)
    assert path == "kernel" and "lane-padded" in reason
    # Multi-device mesh with tp=1: replicated shard_map launch.
    assert paged_dispatch(8, 32, 1024, tp=1, n_devices=8)[0] == "kernel_replicated"
    # kv heads not divisible by tp: replicated too.
    assert paged_dispatch(6, 24, 768, tp=4, n_devices=4)[0] == "kernel_replicated"
    # per-shard folded axis off the lane grid: padded scratch per shard.
    assert paged_dispatch(8, 32, 640, tp=8, n_devices=8)[0] == "kernel_sharded"
    # CPU platform takes the ragged pure-JAX reference (the ONLY
    # remaining organic gather layout).
    path, reason = paged_dispatch(8, 32, 1024, platform="cpu")
    assert path == "gather" and "ragged reference" in reason
    # Proper tp-sharded flagship layout rides the shard_mapped kernel.
    assert paged_dispatch(8, 32, 1024, tp=8, n_devices=8)[0] == "kernel_sharded"


def test_force_flag_precedence():
    assert paged_dispatch(4, 32, 192, force="1")[0] == "kernel"
    assert paged_dispatch(4, 32, 256, force="0")[0] == "gather"
    assert paged_dispatch(8, 32, 1024, tp=8, force="1")[0] == "kernel_sharded"
    # Forced on with non-shardable heads: replicated launch, not a crash
    # inside shard_map (and not the gather fallback anymore).
    assert paged_dispatch(6, 24, 768, tp=4, force="1")[0] == "kernel_replicated"
    # Force=1 wins over platform (interpret mode off-TPU — CPU tests).
    assert paged_dispatch(4, 32, 256, platform="cpu", force="1")[0] == "kernel"


def test_dispatch_matches_live_path_on_cpu():
    """The pure decision function and the real dispatcher agree: on this
    CPU test platform every layout gathers (and still computes the right
    numbers vs the reference oracle)."""
    import jax
    import jax.numpy as jnp

    from inference_gateway_tpu.ops.paged_attention import (
        paged_attention, paged_attention_jax)

    platform = jax.devices()[0].platform
    path, _ = paged_dispatch(4, 8, 256, platform=platform,
                             n_devices=len(jax.devices()))
    assert path == "gather"

    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, ps, P, mp = 2, 8, 4, 64, 16, 8, 2
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, P, (B, mp)), jnp.int32)
    lengths = jnp.asarray([ps * mp, ps], jnp.int32)
    got = paged_attention(q, k, v, pt, lengths, Hkv)
    want = paged_attention_jax(q, k, v, pt, lengths, Hkv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_engine_on_a_tpu_refuses_a_gather_layout(monkeypatch):
    """A paged engine that finds itself on a TPU with its attention on
    the gather fallback — and no IG_TPU_PAGED_KERNEL=0 asking for it —
    must refuse to build, not look healthy and set a gauge. No layout
    dispatches there today, so the table is bent to make one."""
    from inference_gateway_tpu.ops import paged_attention
    from inference_gateway_tpu.serving import engine as engine_mod

    monkeypatch.setattr(engine_mod, "device_info", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1})
    monkeypatch.setattr(paged_attention, "paged_dispatch",
                        lambda *a, **k: ("gather", "a layout nobody wrote a kernel for"))
    cfg = engine_mod.EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64,
                                  dtype="float32", use_mesh=False, attention="paged",
                                  page_size=16)
    with pytest.raises(RuntimeError, match="gather fallback"):
        engine_mod.Engine(cfg)
    # The explicit switch is the one way to ask for it.
    monkeypatch.setattr(paged_attention, "FORCE_PAGED_KERNEL", "0")
    assert engine_mod.Engine(cfg).attention_path == "gather"
