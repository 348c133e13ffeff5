"""The SmallThinker configuration's files (PR 28): the source's keys unchanged,
the cut stated, byte counts against arithmetic by hand, and the three ``moe_*``
readers on hand-built sources."""
import importlib.util
import json
from pathlib import Path

import moe_bytes
import ops_bytes
import reference

BENCH = Path(__file__).resolve().parent.parent
CONFIG = json.loads((BENCH / "configs" / "smallthinker-21b-a3b-int8.json").read_text())
# config.json of PowerInfer/SmallThinker-21BA3B-Instruct, the numbers that shape the model
SOURCE = {"head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
          "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64, "num_attention_heads": 28,
          "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_theta": 1500000, "sliding_window_size": 4096,
          "vocab_size": 151936, "tie_word_embeddings": False, "moe_primary_router_apply_softmax": True,
          "norm_topk_prob": True}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_sources_keys_and_states_its_cut():
    for key, value in SOURCE.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert CONFIG["num_hidden_layers"] == 16 and CONFIG["published"]["num_hidden_layers"] == 52
    assert CONFIG["rope_layout"] == CONFIG["sliding_window_layout"] == [0, 1, 1, 1] * 4   # four whole periods
    # the aliases ops_bytes.py reads repeat the source's moe_* keys, and say so
    for alias, key in (("intermediate_size", "moe_ffn_hidden_size"), ("num_local_experts", "moe_num_primary_experts"),
                       ("num_experts_per_tok", "moe_num_active_primary_experts")):
        assert CONFIG[alias] == CONFIG[key] and key in CONFIG["assumed"][alias]
    assert CONFIG["deployment_sizes"]["tensor_parallel"] == 1 and CONFIG["reference"] == "moe_early_router_gqa"
    ref = reference.load_reference(CONFIG["reference"])
    for model in (CONFIG, CONFIG["rehearsal"]["model"]):
        sizes = ref.sizes(model)
        assert len(sizes["rope_layout"]) == sizes["num_hidden_layers"]
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype) == ("smallthinker-21b-a3b-stage16", "int8", "bfloat16")


def test_byte_counts_by_hand():
    # attention: q and o 2560 x 3584, k and v 2560 x 512; one expert: three matrices of 2560 x 768
    assert ops_bytes.attention_params(CONFIG) == 2 * 2560 * 3584 + 2 * 2560 * 512 == 20_971_520
    assert ops_bytes.expert_params(CONFIG) == 3 * 2560 * 768 == 5_898_240
    # a step that reads every expert: 16 layers of 398,458,880 B and the 2560 x 151,936 head, int8
    assert ops_bytes.weight_bytes_per_step(CONFIG, 64) == 16 * (20_971_520 + 64 * 5_898_240) + 388_956_160 == 6_764_298_240
    assert ops_bytes.kv_bytes_per_token(CONFIG) == 2 * 16 * 4 * 128 * 2 == 32_768
    assert moe_bytes.expert_bytes_per_step(CONFIG, 60) == 16 * 60 * 5_898_240 == 5_662_310_400
    # the whole tree: layers + bf16 embedding + int8 head; the pool: 48 x 2,048 tokens of 32 KiB
    tree = 16 * (20_971_520 + 64 * 5_898_240) + 151_936 * 2560 * 2 + 388_956_160
    assert tree == 7_542_210_560 and 48 * 2048 * 32_768 == 3_221_225_472


def sources(kernels, records, mesh):
    return {"config": CONFIG, "chip": {"hbm_bytes_per_s": 819e9},
            "trace": {"busy_s": 2.0, "expert_kernels": kernels},
            "status": {"mesh": mesh, "jax_trace": {"capture_epoch_ns": [0, 10e9]}},
            "timeline": records}


def test_expert_kernels_are_told_apart_by_the_program_that_encloses_them():
    """Two decode programs and a mixed step on one device line: a kernel belongs
    to the program event its start lies in, whatever its shape; an operand that
    names a kernel does not make a fusion one."""
    import re

    decode = re.compile(r"^jit__decode_chunk_fn")
    mods = [("jit__decode_chunk_fn_paged_ee(1)", 0.0, 1.0), ("jit__mixed_step_fn(2)", 1.5, 0.5),
            ("jit__decode_chunk_fn_paged_ee(1)", 3.0, 1.0), ("jit__set_row(3)", 4.5, 0.1)]
    ops = [("%moe_gate_up_gmm.1 = bf16[288,768]{1,0:T(8,128)(2,1)} custom-call(%a, %b)", 0.1, 0.25),
           ("%moe_down_gmm.2 = bf16[288,2560]{1,0} custom-call(%moe_gate_up_gmm.1)", 0.4, 0.15),
           ("%moe_gate_up_gmm.7 = bf16[3456,768]{1,0} custom-call(%c)", 1.6, 0.1),
           ("%moe_down_gmm.9 = bf16[512,2560]{1,0} custom-call(%d)", 3.2, 0.4),    # another row tile: still decode
           ("%fusion.3 = bf16[48,3584]{1,0} fusion(%moe_down_gmm.2, s8[16,2560,3584] %w)", 0.6, 0.4)]
    got = moe_bytes.attribute(ops, mods, decode)
    assert got == {"all_s": 0.25 + 0.15 + 0.1 + 0.4, "decode_s": 0.25 + 0.15 + 0.4, "moe_events": 4,
                   "decode_programs": 2}
    assert moe_bytes.attribute(ops[4:], mods, decode)["all_s"] == 0.0


def test_the_moe_readers_on_hand_built_sources():
    import pytest

    kernels = {"all_s": 0.9, "decode_s": 0.8, "moe_events": 300, "decode_programs": 10}
    recs = [{"kind": "decode", "steps": 8, "experts_read": 60.0, "start_ts": 1.0, "submit_ts": 0.9},
            {"kind": "decode", "steps": 8, "experts_read": 62.0, "start_ts": 2.0},
            {"kind": "mixed", "steps": 1, "experts_read": 64.0, "start_ts": 3.0},
            {"kind": "decode", "steps": 8, "experts_read": 10.0, "start_ts": 11.0}]   # after the capture
    src = sources(kernels, recs, {"tp": 1})
    assert reader("moe_busy_share").compute(src) == 100.0 * 0.9 / 2.0
    assert reader("experts_read_mean").compute(src) == (60.0 + 62.0 + 10.0) / 3
    # decode's kernels 0.8 s over 10 chunks x 8 steps = 10 ms a step; 61 experts x 16 x 5,898,240 B at 819 GB/s
    want = 100.0 * (16 * 61 * 5_898_240 / 819e9) / 0.010
    assert abs(reader("moe_decode_hbm_roofline_share").compute(src) - want) < 1e-9 and 60 < want < 80
    # the accepted reader takes the same count and mesh
    assert reader("decode_hbm_roofline_share").experts_read(src) == 61.0
    # nothing to read: a program without the layer, the counter or the mesh (the parent commit)
    none = {"all_s": 0.0, "decode_s": 0.0, "moe_events": 0, "decode_programs": 10}
    for bare in (sources(none, recs, {"tp": 1}), sources(kernels, [], {"tp": 1}), sources(kernels, recs, None),
                 sources(kernels, recs, {"ep": 4, "tp": 1}), sources({**kernels, "decode_programs": 0}, recs, {"tp": 1})):
        assert reader("moe_decode_hbm_roofline_share").compute(bare) is None
    assert reader("moe_busy_share").compute(sources(none, recs, None)) is None
    assert reader("experts_read_mean").compute(sources(kernels, [r for r in recs if "experts_read" not in r], None)) is None
    # kernels and decode programs, but none of the first inside the second: a renamed kernel or program
    with pytest.raises(RuntimeError, match="no kernel inside a decode program"):
        reader("moe_decode_hbm_roofline_share").compute(sources({**kernels, "decode_s": 0.0}, recs, {"tp": 1}))
