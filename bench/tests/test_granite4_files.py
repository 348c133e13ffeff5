"""The granite-4.0-h-small configuration's files (PR 50): the catalog row's keys
unchanged but the two in ``reduced``, what was assumed stated, ``mamba2_bytes.py``'s
counts against arithmetic by hand, the four new readers on hand-built sources,
the entries found by name and after Xing4.0's, and the reference child on probes
of a tiny engine whose mixed steps hand every mixer's state over block and
chunk boundaries."""
import importlib.util
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np

import mamba2_bytes
import reference
from test_reference_child import serve_probe

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "granite-4.0-h-small.long-context"
CONFIG_FILE = BENCH / "configs" / "granite-4.0-h-small-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# `config` of the catalog's row (architectures.jsonl, granite-4.0-h-small), every key
SOURCE = {"attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12, "hidden_act": "silu",
          "hidden_size": 4096, "intermediate_size": 768, "layer_types": PERIOD + 3 * (["mamba"] * 5 + ["attention"] + ["mamba"] * 4),
          "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
          "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
          "max_position_embeddings": 131072, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
          "num_attention_heads": 32, "num_experts_per_tok": 10, "num_hidden_layers": 40, "num_key_value_heads": 8,
          "num_local_experts": 72, "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
          "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 1536, "tie_word_embeddings": True,
          "vocab_size": 100352}
REDUCED = {"num_hidden_layers": 10, "layer_types": PERIOD}
NEW_READERS = ["ssd_busy_share", "ssd_chunk_roofline_share", "ssd_state_hbm_roofline_share", "granite_mixed_step_mfu"]


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_rows_keys_but_the_two_reduced_and_states_what_it_assumed():
    assert [i for i, k in enumerate(SOURCE["layer_types"]) if k == "attention"] == [5, 15, 25, 35]
    for key, value in SOURCE.items():
        want = REDUCED.get(key, value)
        assert CONFIG[key] == want and type(CONFIG[key]) is type(want), key
    assert CONFIG["reduced"] == list(REDUCED) and set(CONFIG["published"]) >= set(REDUCED)
    assert CONFIG["source"].endswith("ibm-granite/granite-4.0-h-small/blob/main/config.json")
    for said in ("head_dim", "expert_width", "in_proj_order", "gated_norm", "delta", "mamba_init", "router", "experts",
                 "multipliers", "nope", "quantisation", "seeding", "ssd_block", "tokenizer", "context"):
        assert CONFIG["assumed"][said], said
    assert CONFIG["assumed_mechanisms"] == {"norm_gate": True, "shared_mlp": True, "state_restart_from_end": 0,
                                            "state_restart_every": 0}
    assert "first of four pipeline stages of 10 layers" in CONFIG["deployment"] and "tensor_parallel 1" in CONFIG["deployment"]
    assert CONFIG["sidecar_args"] == ["--max-slots", "32", "--max-seq-len", "12800", "--page-size", "128",
                                      "--quantize", "int8", "--attention", "paged", "--dtype", "bfloat16"]
    probes = CONFIG["probes"]
    assert probes["before_window_chars"] == [3000] and probes["in_window_chars"] == [1500, 2500, 5000, 9000]
    tiny = CONFIG["rehearsal"]
    assert tiny["preset"] == "granite4-test-tiny" and CONFIG["preset"] == "granite-4.0-h-small-stage10"
    ref = reference.load_reference(CONFIG["reference"])
    assert CONFIG["reference"] == "mamba2_moe_gqa" and CONFIG["deployment_sizes"]["state_dtype"] == "float32"
    for model in (CONFIG, tiny["model"]):
        s = ref.sizes(model)
        assert (s["embedding_multiplier"], s["residual_multiplier"], s["logits_scaling"], s["mamba_d_conv"]) == (12, 0.22, 16, 4)
        assert s["norm_gate"] is True and s["shared_mlp"] is True and not s["state_restart_from_end"]
        assert s["mamba_n_heads"] * s["mamba_d_head"] == 2 * s["hidden_size"] and s["layer_types"].count("attention") == 1
    assert ref.sizes(CONFIG)["head_dim"] == 128 and ref.sizes(CONFIG)["attention_multiplier"] == 1 / 128
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == (
        "granite-4.0-h-small-stage10", "int8", "bfloat16", "dense", 1)
    # the program's preset holds the file's sizes
    sys.path.insert(0, str(ROOT))
    from inference_gateway_tpu.models import mixtral

    for preset, model in ((CONFIG["preset"], CONFIG), (tiny["preset"], tiny["model"])):
        p = mixtral.PRESETS[preset]
        assert ["attention" if f == 0 else "mamba" for f in p.layer_conv] == model["layer_types"]
        assert (p.hidden_size, p.num_heads, p.num_kv_heads, p.vocab_size, p.intermediate_size, p.num_experts,
                p.experts_per_token) == tuple(model[k] for k in (
                    "hidden_size", "num_attention_heads", "num_key_value_heads", "vocab_size", "intermediate_size",
                    "num_local_experts", "num_experts_per_tok"))
        assert (p.ssm_heads, p.ssm_head_dim, p.ssm_state, p.conv_kernel, p.shared_experts * p.intermediate_size) == tuple(
            model[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv", "shared_intermediate_size"))
        assert (p.embedding_multiplier, p.attention_multiplier, p.residual_multiplier, p.logits_scaling, p.rms_norm_eps) == tuple(
            model[k] for k in ("embedding_multiplier", "attention_multiplier", "residual_multiplier", "logits_scaling",
                               "rms_norm_eps"))
    assert mixtral.PRESETS[CONFIG["preset"]].ssd_block == CONFIG["deployment_sizes"]["ssd_block"] == 128
    assert mixtral.PRESETS[tiny["preset"]].ssd_block == tiny["deployment_sizes"]["ssd_block"] == 8


def test_the_benchmark_gains_one_configuration_one_cell_and_four_readers_after_xing4s():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # found by name, and after the entries the parent had: a later PR's appended entry must not fail this
    index = lambda entries, name: [e["name"] for e in entries].index(name)
    config = bench["configs"][index(bench["configs"], "granite-4.0-h-small-int8")]
    assert index(bench["configs"], config["name"]) > index(bench["configs"], "xing4.0-29b-a4b-int8")
    assert config["reduced"] == CONFIG["reduced"] == ["num_hidden_layers", "layer_types"] and config["source"] == CONFIG["source"]
    assert config["file"] == "bench/configs/granite-4.0-h-small-int8.json"
    assert index(bench["workloads"], CELL) > index(bench["workloads"], "xing4.0-29b-a4b.long-context")
    cell = bench["workloads"][index(bench["workloads"], CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "granite-4.0-h-small-int8", "longctx-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    first = index(bench["per_layer"], NEW_READERS[0])
    assert first > index(bench["per_layer"], "post_warmup_compile_s")
    assert [m["name"] for m in bench["per_layer"][first:first + 4]] == NEW_READERS
    for m in bench["per_layer"][first:first + 4]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert m["better"] == ("lower" if m["name"].endswith("busy_share") else "higher")
        assert m["layer"] in {o["layer"] for o in bench["per_layer"][:first]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # every step of the window is a mixed step: the readers of decode records stay off; the hybrid's readers take
    # any file with `mamba_d_state` for SambaY's family and its sizes; fetch_return_ms reads null everywhere
    for name in ("decode_step_ms", "decode_batch_mean", "experts_read_mean", "starved_slot_share", "fetch_return_ms",
                 "decode_hbm_roofline_share", "moe_decode_hbm_roofline_share", "tpot_p95_ms", "idle_unspanned_share",
                 "ssm_busy_share", "hybrid_decode_hbm_roofline_share", "window_skip_share", "shortconv_busy_share",
                 "mla_attn_busy_share"):
        assert CELL not in by_name[name]["workloads"], name
    # the cell is appended to the lists of the accepted readers its traced runs report, behind the cells the parent had
    accepted = [w["name"] for w in bench["workloads"][:index(bench["workloads"], CELL)]]
    for name in ("ttft_p50_ms.batch", "mixed_step_host_ms.batch", "attn_kernel_busy_share", "host_starved_share",
                 "idle_unexplained_share", "start_import_s", "start_engine_build_s", "moe_busy_share",
                 "post_warmup_compile_s"):
        listed = by_name[name]["workloads"]
        assert all(listed.index(CELL) > listed.index(w) for w in accepted if w in listed), name
    mix = json.loads((BENCH / "traffic" / "longctx-closed.json").read_text())        # the accepted file, unedited
    assert (mix["loop"], mix["schedule_seed"], mix["ramp_s"], mix["max_total_tokens"]) == ("closed", 36, 8, 12700)
    clients = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"]
    assert clients == CONFIG["deployment_sizes"]["max_slots"] == 32                  # one client a slot


def test_byte_counts_by_hand():
    in_proj, out_proj = 4096 * (8192 + 8448 + 128), 8192 * 4096                                         # int8
    shared, experts, router = 3 * 4096 * 1536, 72 * 3 * 4096 * 768, 4096 * 72 * 2
    assert (in_proj, out_proj, shared, experts, router) == (68_681_728, 33_554_432, 18_874_368, 679_477_248, 589_824)
    mamba_layer = in_proj + out_proj + shared + experts + router
    attention = 4096 * (2 * 32 * 128 + 2 * 8 * 128)
    attention_layer = attention + shared + experts + router
    assert (mamba_layer, attention, attention_layer) == (801_177_600, 41_943_040, 740_884_480)
    layers, embed = 9 * mamba_layer + attention_layer, 100352 * 4096 * 2
    assert (layers, embed) == (7_951_482_880, 822_083_584)
    state, tails = 128 * 64 * 128 * 4, 3 * 8448 * 4
    slot = 9 * (state + tails)
    assert (state, slot, 32 * slot) == (4_194_304, 38_661_120, 1_237_155_840)
    pool = 32 * 12800 * 1 * 2 * 8 * 128 * 2
    assert pool == 1_677_721_600
    assert all(f"{n:,}" in CONFIG["deployment"] for n in (
        in_proj, out_proj, shared, experts, router, mamba_layer, attention, attention_layer, layers, embed, state, slot,
        32 * slot, pool))
    assert round((layers + embed + 32 * slot + pool) / 1e9, 2) == 11.69 and "11.69 GB" in CONFIG["deployment"]
    assert mamba2_bytes.state_bytes_per_row_layer(CONFIG) == state
    s = mamba2_bytes.sizes(CONFIG)
    assert (s["mamba"], s["attention"], s["Di"], s["Q"], s["slots"], s["tokens"]) == (9, 1, 8192, 128, 32, 544)
    # a visit of a block of 128 tokens: three products a head and C B^T once; x in, y out, B and C
    assert mamba2_bytes.block_flops(CONFIG) == 128 * 3 * 2 * 64 * 128 * 128 + 2 * 128 * 128 * 128 == 809_500_672
    assert mamba2_bytes.block_bytes(CONFIG) == (2 * 128 * 8192 + 2 * 128 * 128) * 4 == 8_519_680
    assert mamba2_bytes.block_flops(CONFIG) / mamba2_bytes.block_bytes(CONFIG) < 197e12 / 819e9        # under the ridge
    # a packed token: 204.5 MFLOP of a Mamba layer's projections, 83.9 of the attention layer's, 226.5 of every FFN
    mixer, attn, ffn = 2 * in_proj + 2 * out_proj, 2 * attention, 10 * 3 * 2 * 4096 * 768 + 3 * 2 * 4096 * 1536
    assert (mixer, attn, ffn) == (204_472_320, 83_886_080, 226_492_416)
    assert mamba2_bytes.token_flops(CONFIG) == 9 * mixer + attn + 10 * ffn == 4_189_061_120
    # a mixed step: a 513-token chunk behind 31 one-token rows (five packed blocks), 32 rows, 200k tokens of context
    record = {"kind": "mixed", "steps": 1, "batch": 32, "ssd_blocks": 45.0, "state_rows": 31.0, "attn_wide_tokens": 513,
              "context_tokens": 200_000}
    want = 544 * 4_189_061_120 + 45 * 809_500_672 + 4 * 32 * 128 * 200_000 + 32 * 2 * 4096 * 100352
    assert mamba2_bytes.mixed_step_flops(CONFIG, record) == want and 2.3e12 < want < 2.4e12
    assert mamba2_bytes.mixed_step_flops(CONFIG, {k: v for k, v in record.items() if k != "ssd_blocks"}) is None
    assert mamba2_bytes.chunk_work(CONFIG, [record, record]) == (90 * 809_500_672, 90 * 8_519_680)
    assert mamba2_bytes.chunk_work(CONFIG, [{"kind": "mixed"}]) is None and mamba2_bytes.chunk_work(CONFIG, []) is None
    decode = {"kind": "decode", "steps": 8, "state_rows": 256.0}                         # 32 rows x 8 steps, summed
    assert mamba2_bytes.update_bytes(CONFIG, [record, decode]) == (31 + 256) * 9 * 2 * state
    assert mamba2_bytes.update_bytes(CONFIG, [{"kind": "decode", "steps": 8}]) is None


def test_the_four_readers_on_hand_built_sources():
    recs = [{"kind": "mixed", "steps": 1, "start_ts": 10.0 + i, "batch": 32, "ssd_blocks": 45.0, "state_rows": 30.0 + i,
             "attn_wide_tokens": 514 - i, "context_tokens": 200_000 + 1000 * i} for i in range(5)]
    recs.append({"kind": "mixed", "steps": 1, "start_ts": 15.0})                       # a record without the counts
    capture = {"capture_epoch_ns": [10.5e9, 13.5e9]}
    chip = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"busy_s": 2.9, "mixed_step_s": 0.07, "ssd_ops": {"seconds": 0.5, "events": 9000},
             "ssd_chunk_kernel": {"seconds": 0.012, "events": 27}, "ssm_state_update_ops": {"seconds": 0.016, "events": 200}}
    src = {"config": CONFIG, "timeline": recs, "chip": chip, "status": {"jax_trace": capture}, "trace": trace}
    inside = recs[1:4]
    assert reader("ssd_busy_share").compute(src) == 100.0 * 0.5 / 2.9
    chunk = reader("ssd_chunk_roofline_share")
    flops, moved = 3 * 45 * 809_500_672, 3 * 45 * 8_519_680
    assert chunk.compute(src) == 100.0 * max(flops / 197e12, moved / 819e9) / 0.012 and 5.0 < chunk.compute(src) < 100.0
    state = reader("ssd_state_hbm_roofline_share")
    rows = sum(r["state_rows"] for r in inside)
    assert state.compute(src) == 100.0 * (rows * 9 * 2 * 4_194_304 / 819e9) / 0.016 and 10.0 < state.compute(src) < 100.0
    mfu = reader("granite_mixed_step_mfu")
    counts = sorted(mamba2_bytes.mixed_step_flops(CONFIG, r) for r in inside)
    assert mfu.compute(src) == 100.0 * (counts[1] / 197e12) / 0.07 and 10.0 < mfu.compute(src) < 100.0
    # nothing to read: no trace, another configuration, no capture window, a record without a counter, and
    # a trace that holds no such operation (the parent commit's program)
    other = json.loads((BENCH / "configs" / "xing4.0-29b-a4b-int8.json").read_text())
    empty = {"busy_s": 2.9, "mixed_step_s": 0.07, "ssd_ops": {"seconds": 0.0, "events": 0},
             "ssd_chunk_kernel": {"seconds": 0.0, "events": 0}, "ssm_state_update_ops": {"seconds": 0.0, "events": 0}}
    for name in NEW_READERS:
        r = reader(name)
        assert r.compute({**src, "trace": None}) is None and r.compute({**src, "config": other}) is None, name
        if name != "granite_mixed_step_mfu":
            assert r.compute({**src, "trace": empty}) is None, name
    for r in (chunk, state, mfu):
        assert r.compute({**src, "status": {}}) is None
        assert r.compute({**src, "timeline": [recs[1], {**recs[5], "start_ts": 12.0}]}) is None
    assert mfu.compute({**src, "trace": {"busy_s": 2.9}}) is None
    # what the patterns find in an instruction's text (events of the compiled step programs, PR 50)
    from mla_bytes import kernel_pattern

    one = re.compile(kernel_pattern(mamba2_bytes.CHUNK_KERNEL)).search
    assert one("%ssd_chunk_kernel.10 = (f32[8192,640]{1,0:T(8,128)}, f32[9,32,128,64,128]{4,3,2,1,0}) custom-call(%a)")
    assert not one("%fusion.3 = f32[544,8192]{1,0} fusion(%ssd_chunk_kernel.10)")
    found = re.compile(mamba2_bytes.update_pattern(CONFIG)).search
    assert found("%select_dynamic-update-slice_fusion.4 = f32[9,32,128,64,128]{4,3,2,1,0:T(8,128)} fusion(%g, %s)")
    assert found("%fusion.500 = f32[32,128,64]{2,1,0:T(8,128)S(1)} fusion(%get-tuple-element.3441, %select_n.1026)")
    assert found("%ssm_state_update_kernel.10 = (f32[32,1,8192]{2,1,0:T(1,128)}, f32[9,32,8192,128]{3,2,1,0}) custom-call(%o)")
    assert not found("%ssd_chunk_kernel.10 = (f32[8192,640]{1,0}, f32[9,32,128,64,128]{4,3,2,1,0}) custom-call(%a)")
    assert not found("%while.59 = (s32[], bf16[1,544,4096], f32[9,32,3,8448], f32[9,32,128,64,128]) while(%t)")
    assert not found("%fusion.9 = bf16[544,4096]{1,0} fusion(bf16[544,8192]{1,0} %p0, s8[9,8192,4096] %p1)")
    found = re.compile(mamba2_bytes.conv_pattern(CONFIG)).search
    assert found("%bitcast_dynamic-update-slice_fusion.4 = f32[9,32,3,8448]{3,1,2,0:T(8,128)} fusion(%get-tuple-element.1)")
    assert found("%fusion.640 = f32[544,8448]{0,1:T(8,128)} fusion(%p)") and found("%fusion.7 = f32[32,4,8448]{2,1,0} fusion(%p)")
    assert not found("%fusion.641 = bf16[544,16768]{1,0} fusion(%p)")
    found = re.compile(mamba2_bytes.scan_pattern(CONFIG)).search
    assert found("%ssd_chunk_kernel.11 = (f32[8192,640]{1,0}, f32[9,32,128,64,128]{4,3,2,1,0}) custom-call(%a)")
    assert found("%pad_bitcast_fusion.8 = f32[8192,640]{1,0:T(8,128)} fusion(%p)") and found("%fusion.640 = f32[544,8448]{0,1} fusion(%p)")
    assert found("%fusion.500 = f32[32,128,64]{2,1,0} fusion(%a)") and found("%ssm_state_update_kernel.11 = (f32[32,1,8192]) custom-call(%o)")
    assert not found("%while.59 = (s32[], bf16[1,544,4096], f32[9,32,3,8448], f32[9,32,128,64,128]) while(%t)")
    assert not found("%moe_gate_up_gmm.3 = bf16[6528,768]{1,0} custom-call(%x)")


def test_reference_child_on_probes_of_a_tiny_engine(tmp_path):
    """Probes of 85-225 prompt tokens served through mixed steps of 64 tokens
    (eight blocks of 8 a chunk, each probe's states handed over block and chunk
    boundaries beside one-token rows) and decode chunks by the rehearsal's own
    engine; then ``reference.py --rehearse-cpu`` as ``run.py`` starts it, on the
    file as it is: the UNEDITED child finds every leaf the reference declares in
    the program's three stacks. Then a control through the same child: the
    state lost in front of the decode steps, NOT ok."""
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    run_cfg = CONFIG["rehearsal"]
    args = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    engine = Engine(EngineConfig(
        model=run_cfg["preset"], quantize=args["--quantize"], dtype=args["--dtype"], attention=args["--attention"],
        page_size=int(args["--page-size"]), max_slots=int(args["--max-slots"]), max_seq_len=int(args["--max-seq-len"]),
        mixed_step=True, mixed_step_tokens=int(run_cfg["sidecar_env"]["SERVING_MIXED_STEP_TOKENS"])))
    assert engine.mixed_ok and engine.carries_state and set(engine.cache) == {"k", "v", "conv", "ssm"}
    rng = np.random.default_rng(50)
    scheduler = Scheduler(engine)
    scheduler.start()
    try:
        probes = [serve_probe(scheduler, CONFIG, "".join(chr(c) for c in rng.integers(32, 127, size=chars)), 8)
                  for chars in (60, 100, 200)]
    finally:
        scheduler.stop()
    probes_file = tmp_path / "probes.json"
    probes_file.write_text(json.dumps({"bias_ids": CONFIG["logit_bias"]["ids"],
                                       "bias": CONFIG["logit_bias"]["value"], "probes": probes}))

    def child(config_file):
        out = subprocess.run([sys.executable, str(BENCH / "reference.py"), str(config_file), str(probes_file),
                              "--rehearse-cpu"], capture_output=True, timeout=300)
        return out, json.loads(out.stdout.decode().strip().splitlines()[-1])

    out, result = child(CONFIG_FILE)
    assert "error" not in result and result["comparisons"] == 24 and result["platform"] == "cpu", (
        result, out.stderr.decode()[-2000:])
    assert result["ok"] and out.returncode == 0, result
    model = {**run_cfg["model"], "assumed_mechanisms": {**run_cfg["model"]["assumed_mechanisms"], "state_restart_from_end": 7}}
    control_file = tmp_path / "control.json"
    control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**run_cfg, "model": model}}))
    out, control = child(control_file)
    assert out.returncode == 1 and control["ok"] is False, control
    assert control["mean_abs_diff"] > 5 * result["mean_abs_diff"] and control["max_abs_diff"] > 1.0, control
