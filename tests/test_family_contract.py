"""What a model family's serving path carries is said once, by its model module
(models/family.py, ISSUE 58). ``tests/family_contract.json`` was captured on the
parent commit (25f1e56), where ``serving/engine.py`` held it as nine refusal
blocks, eight stats tuples and five status writers: for every preset the type and
text of each refusal, the attention layers by window, and for every tiny preset
the family blocks of ``/debug/status`` (as ``serving/server.py`` merged them) and
the facts of a paged engine. The record is held to that capture, to the letter
(ISSUE 63 added ``one_token_update`` to the ``ssd`` and ``kda`` blocks, the
one-token kernels' plan for the engine's store; the capture holds it since).
"""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from inference_gateway_tpu.models.family import family_of, preset_config
from inference_gateway_tpu.serving import engine as engine_mod
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, choose_mesh

PARENT = json.loads((Path(__file__).parent / "family_contract.json").read_text())
ASK = {"spec_draft": {"spec_draft": "ngram"}, "vision_model": {"vision_model": "vit-test-tiny"},
       "int4": {"quantize": "int4"}}
TINY = sorted(PARENT["facts"])


class _Built(Exception):
    """The engine got as far as building its weights: nothing was refused."""


@pytest.fixture
def no_build(monkeypatch):
    def built(*_a, **_k):
        raise _Built()

    monkeypatch.setattr(engine_mod, "build_params", built)


@pytest.mark.parametrize("feature", [*ASK, "mesh"])
@pytest.mark.parametrize("preset", sorted(PARENT["refusals"]))
def test_a_refusal_reads_as_on_the_parent(preset, feature, no_build):
    """Refused by name before anything is built, with the parent's words; what the
    parent did not refuse gets as far as the weights (or a mesh)."""
    expected = PARENT["refusals"][preset][feature]
    cfg = preset_config(preset)
    if feature == "mesh":
        attempt = lambda: choose_mesh(EngineConfig(model=preset, mesh_shape={"tp": 2}), cfg, 8)
    else:
        attempt = lambda: Engine(EngineConfig(model=preset, attention="paged", **ASK[feature]))
    if expected is None:
        assert feature not in family_of(cfg).refuses
        if feature != "mesh":
            with pytest.raises(_Built):
                attempt()
        return
    with pytest.raises(ValueError) as e:
        attempt()
    assert [type(e.value).__name__, str(e.value)] == expected
    if feature == "mesh":   # left to the default it is served on one device
        assert choose_mesh(EngineConfig(model=preset), cfg, 8) == (None, False)


@pytest.mark.parametrize("preset", sorted(PARENT["layers"]))
def test_the_attention_layers_by_window_read_as_on_the_parent(preset):
    assert [list(kind) for kind in family_of(preset_config(preset)).attention_layers] == PARENT["layers"][preset]


@pytest.fixture(scope="module")
def engines():
    built = {}

    def engine(preset: str) -> Engine:
        if preset not in built:
            # (pages of 16 rows, but where a family's pool is laid out for one size: ISSUE 61)
            built[preset] = Engine(EngineConfig(
                model=preset, attention="paged", page_size=getattr(family_of(preset_config(preset)).cached_rows, "page_size", 16),
                max_slots=2, max_seq_len=64, dtype="float32", use_mesh=False, prefill_buckets=(16, 32)))
        return built[preset]

    return engine


@pytest.mark.parametrize("preset", TINY)
def test_the_status_blocks_read_as_on_the_parent(preset, engines):
    """Key for key and value for value, in the parent's order; a drafting model's
    ``mtp`` with a scheduler's sums and without."""
    eng = engines(preset)
    rounds = types.SimpleNamespace(spec_slot_rounds=8, spec_accepted=5, spec_emitted=13)
    status = eng.family_status(rounds)
    assert status == PARENT["status"][preset] and list(status) == list(PARENT["status"][preset])
    alone = PARENT["status"].get(preset + "|no scheduler")
    if alone is not None:
        assert {k: eng.family_status()[k] for k in alone} == alone


@pytest.mark.parametrize("preset", TINY)
def test_the_engine_reads_as_on_the_parent(preset, engines):
    """The four attributes ``bench/tests`` assert, the pool's arrays, the module, the
    plans; and the stat names are as many as the fetch block's counted rows."""
    eng, was = engines(preset), PARENT["facts"][preset]
    now = {
        "mixed_ok": eng.mixed_ok, "carries_state": eng.carries_state, "is_mla": eng.is_mla, "mtp": eng.mtp,
        "cache": sorted(eng.cache), "round_tokens": eng.round_tokens, "mixed_budget": eng.mixed_budget,
        "mixed_stats": list(eng._mixed_stats), "chunk_stats": list(eng._chunk_stats),
        "last_block": list(eng._last_block.shape), "module": eng._model.__name__,
        "ragged_plan": eng.ragged_attention_plan is not None, "decode_plan": eng.decode_attention_plan is not None,
        "prefix_declines": eng.prefix_cache.decline_hits, "is_moe": eng.is_moe,
        "state_resets": "state_resets" in eng.metrics,
    }
    assert now == was
    family = eng.family
    assert eng._last_block.shape[0] == 2 + len(family.mixed_stats)
    assert (eng._mixed_stats, eng._chunk_stats) == (family.mixed_stats, family.chunk_stats)
    assert len(family.mixed_stats) - len(family.chunk_stats) == (2 if family.experts else 0)
