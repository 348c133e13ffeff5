"""Mixtral MoE tests: numerics vs HF, dispatch paths, EP sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import mixtral
from inference_gateway_tpu.ops.moe import moe_dense, moe_dropless, router_topk


@pytest.fixture(scope="module")
def hf_tiny():
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig as HFMixtralConfig
    from transformers import MixtralForCausalLM

    hf_cfg = HFMixtralConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=96, num_local_experts=4,
        num_experts_per_tok=2, max_position_embeddings=512, rms_norm_eps=1e-5,
    )
    torch.manual_seed(0)
    model = MixtralForCausalLM(hf_cfg).eval()
    return hf_cfg, model


def test_router_topk():
    logits = jnp.asarray([[1.0, 3.0, 2.0, -1.0]])
    w, idx = router_topk(logits, 2)
    assert list(np.asarray(idx[0])) == [1, 2]
    np.testing.assert_allclose(float(w.sum()), 1.0, rtol=1e-6)


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas_interpret"])
def test_dropless_matches_dense(kernel):
    """The dropless layer against the all-experts oracle (re-pointed from
    the capacity layer's no-drop case, PR 28): stacked leaves, layer 1 of 2."""
    rng = np.random.default_rng(0)
    N, H, I, E, k, L = 16, 8, 12, 4, 2, 2
    x = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32))
    wg, wu = (jnp.asarray(rng.normal(size=(L, E, H, I)).astype(np.float32) * 0.1) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(L, E, I, H)).astype(np.float32) * 0.1)

    def expert_fn(inp):  # (E, n, H)
        h = jax.nn.silu(jnp.einsum("enh,ehi->eni", inp, wg[1])) * jnp.einsum("enh,ehi->eni", inp, wu[1])
        return jnp.einsum("eni,eih->enh", h, wd[1])

    dense = moe_dense(x, logits, k, expert_fn)
    out, rows = moe_dropless(x, logits, k, wg, wu, wd, 1, kernel=kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=1e-4, atol=1e-5)
    assert int(rows.sum()) == N * k


def test_logits_match_hf(hf_tiny):
    import torch

    from inference_gateway_tpu.models.hf_loader import mixtral_config_from_hf, mixtral_params_from_hf

    hf_cfg, model = hf_tiny
    cfg = mixtral_config_from_hf(hf_cfg)
    # The all-experts oracle, as HF computes it.
    cfg = mixtral.MixtralConfig(
        **{**cfg.__dict__, "moe_impl": "dense", "rope_scaling": cfg.rope_scaling}
    )
    params = mixtral_params_from_hf(model.state_dict(), cfg, dtype=jnp.float32)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(2, 7))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()

    B, T = tokens.shape
    positions = np.broadcast_to(np.arange(T), (B, T)).copy()
    ours, _ = mixtral.forward(
        params, cfg, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray([T, T]),
        mode="prefill",
    )
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=3e-4, atol=3e-4)


def test_prefill_decode_cache_consistency():
    cfg = mixtral.PRESETS["mixtral-test-tiny"]
    params = mixtral.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    B, P, Tot, S = 2, 4, 7, 16
    tokens = jnp.asarray(rng.integers(0, 256, size=(B, Tot)))

    positions = jnp.broadcast_to(jnp.arange(Tot), (B, Tot))
    full, _ = mixtral.forward(params, cfg, tokens, positions, jnp.full((B,), Tot), mode="prefill")

    cache = mixtral.init_cache(cfg, B, S, dtype=jnp.float32)
    pre_pos = jnp.broadcast_to(jnp.arange(P), (B, P))
    _, cache = mixtral.forward(params, cfg, tokens[:, :P], pre_pos, jnp.full((B,), P), cache, mode="prefill")
    for t in range(P, Tot):
        logits, cache = mixtral.forward(
            params, cfg, tokens[:, t:t + 1], jnp.full((B, 1), t), jnp.full((B,), t + 1),
            cache, mode="decode",
        )
        # Summation order differs between batched prefill and single-token
        # decode, so allow small numerical drift.
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, t]), rtol=1e-3, atol=1e-3)


def test_ep_sharded_forward_matches_single_device():
    from inference_gateway_tpu.parallel.mesh import create_moe_mesh
    from inference_gateway_tpu.parallel.sharding import named

    cfg = mixtral.PRESETS["mixtral-test-tiny"]
    params = mixtral.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)

    rng = np.random.default_rng(2)
    B, T = 4, 8
    tokens = jnp.asarray(rng.integers(0, 256, (B, T)))
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    lengths = jnp.full((B,), T)
    ref, _ = mixtral.forward(params, cfg, tokens, positions, lengths, mode="prefill")

    mesh = create_moe_mesh(dp=2, sp=1, ep=2, tp=2)  # 8 devices
    sharded = jax.device_put(params, named(mesh, mixtral.param_specs(cfg)))
    with jax.sharding.set_mesh(mesh):
        out, _ = mixtral.forward(sharded, cfg, tokens, positions, lengths, mode="prefill")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
