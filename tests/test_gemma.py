"""Gemma family: numerics vs HF, serving smoke."""

import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import llama
from inference_gateway_tpu.models.hf_loader import llama_config_from_hf, llama_params_from_hf
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import Scheduler, generate_sync


def test_gemma_logits_match_hf():
    torch = pytest.importorskip("torch")
    from transformers import GemmaConfig, GemmaForCausalLM

    hf_cfg = GemmaConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=1, intermediate_size=128, head_dim=16,
        max_position_embeddings=512, rms_norm_eps=1e-6,
    )
    torch.manual_seed(0)
    model = GemmaForCausalLM(hf_cfg).eval()

    cfg = llama_config_from_hf(hf_cfg)
    assert cfg.norm_offset and cfg.embed_scale and cfg.hidden_act == "gelu_tanh"
    assert cfg.hd == 16
    params = llama_params_from_hf(model.state_dict(), cfg, dtype=jnp.float32)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(2, 7))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()

    B, T = tokens.shape
    positions = np.broadcast_to(np.arange(T), (B, T)).copy()
    ours, _ = llama.forward(params, cfg, jnp.asarray(tokens), jnp.asarray(positions),
                            jnp.asarray([T, T]), mode="prefill")
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=4e-4, atol=4e-4)


def test_gemma_engine_serves():
    e = Engine(EngineConfig(model="gemma-test-tiny", max_slots=2, max_seq_len=64,
                            dtype="float32", max_prefill_batch=2, use_mesh=False))
    s = Scheduler(e)
    s.start()
    try:
        out, _ = generate_sync(s, [3, 5, 7, 11], max_tokens=5, temperature=0.0)
        assert len(out) == 5
    finally:
        s.stop()


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_a_seeded_gemma_norm_has_an_effective_weight_of_one(quantize):
    """A model that adds one to a norm's stored weight (``norm_offset``) is seeded with
    zeros there, in both initialisers: stored ones would double every norm (ISSUE 61)."""
    e = Engine(EngineConfig(model="gemma-test-tiny", max_slots=1, max_seq_len=16, dtype="float32",
                            quantize=quantize, use_mesh=False))
    norms = [e.params["layers"]["attn_norm"], e.params["layers"]["mlp_norm"], e.params["final_norm"]]
    assert all(float(jnp.abs(llama._nw(w, e.model_cfg) - 1.0).max()) == 0.0 for w in norms)
