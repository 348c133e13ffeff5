"""The Xing4.0-29B-A4B configuration's files (PR 47): the catalog row's keys
unchanged but the two in ``reduced``, what was assumed stated, ``mla_bytes.py``'s
counts against arithmetic by hand, the four new readers on hand-built sources,
the entries found by name and after LFM2's, and the reference child on probes of
a tiny engine whose mixed steps put absorbed decode rows beside chunk tiles."""
import importlib.util
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np

import mla_bytes
import reference
from test_reference_child import serve_probe

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "xing4.0-29b-a4b.long-context"
CONFIG_FILE = BENCH / "configs" / "xing4.0-29b-a4b-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
# `config` of the catalog's row (architectures.jsonl, Xing4.0-29B-A4B), every key
SOURCE = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu", "hidden_size": 3584,
          "intermediate_size": 9216, "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "xing4_0",
          "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
          "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
          "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
          "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
          "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
          "rope_theta": 10000,
          "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                           "original_max_position_embeddings": 4096, "type": "yarn"},
          "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
          "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 10, "num_nextn_predict_layers": 0}
NEW_READERS = ["mla_step_hbm_roofline_share", "mla_attn_roofline_share", "mla_attn_busy_share", "mhc_busy_share"]


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_rows_keys_but_the_two_reduced_and_states_what_it_assumed():
    for key, value in SOURCE.items():
        want = REDUCED.get(key, value)
        assert CONFIG[key] == want and type(CONFIG[key]) is type(want), key
    assert CONFIG["reduced"] == list(REDUCED) and set(CONFIG["published"]) >= set(REDUCED)
    assert CONFIG["source"].endswith("XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")
    for said in ("hc_entry", "hc_exit", "hc_maps", "hc_clamp", "hc_sinkhorn", "hc_input_norm", "mla", "rotary", "yarn",
                 "router", "shared_expert", "dense_layers", "weights", "tokenizer", "context", "pool_row"):
        assert CONFIG["assumed"][said], said
    assert CONFIG["assumed_mechanisms"] == {"residual_mixing": True, "rope_scores": True}
    assert "first of four pipeline stages of 10 layers" in CONFIG["deployment"] and "tensor_parallel 1" in CONFIG["deployment"]
    assert CONFIG["sidecar_args"] == ["--max-slots", "32", "--max-seq-len", "12800", "--page-size", "128",
                                      "--quantize", "int8", "--attention", "paged", "--dtype", "bfloat16"]
    probes = CONFIG["probes"]
    assert probes["before_window_chars"] == [3000] and probes["in_window_chars"] == [1500, 2500, 5000, 9000]
    tiny = CONFIG["rehearsal"]
    assert tiny["preset"] == "xing4-test-tiny" and CONFIG["preset"] == "xing4.0-stage10"
    ref = reference.load_reference(CONFIG["reference"])
    assert CONFIG["reference"] == "mhc_mla_moe"
    for model in (CONFIG, tiny["model"]):
        s = ref.sizes(model)
        assert (s["hc_mult"], s["hc_sinkhorn_iters"], s["first_k_dense_replace"], s["routed_scaling_factor"]) == (4, 20, 2, 2)
        assert s["residual_mixing"] is True and s["rope_scores"] is True and s["matrix_rounding"] is None
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == (
        "xing4.0-stage10", "int8", "bfloat16", "dense", 1)


def test_the_benchmark_gains_one_configuration_one_cell_and_four_readers_after_lfm2s():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # found by name, and after the entries the parent had: a later PR's appended entry must not fail this
    index = lambda entries, name: [e["name"] for e in entries].index(name)
    config = bench["configs"][index(bench["configs"], "xing4.0-29b-a4b-int8")]
    assert index(bench["configs"], config["name"]) > index(bench["configs"], "lfm2-8b-a1b-int8")
    assert config["reduced"] == CONFIG["reduced"] and config["source"] == CONFIG["source"]
    assert config["file"] == "bench/configs/xing4.0-29b-a4b-int8.json"
    assert index(bench["workloads"], CELL) > index(bench["workloads"], "lfm2-8b-a1b.reason")
    cell = bench["workloads"][index(bench["workloads"], CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "xing4.0-29b-a4b-int8", "longctx-closed", 1)
    first = index(bench["per_layer"], NEW_READERS[0])
    assert first > index(bench["per_layer"], "shortconv_busy_share")
    assert [m["name"] for m in bench["per_layer"][first:first + 4]] == NEW_READERS
    for m in bench["per_layer"][first:first + 4]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert m["better"] == ("lower" if m["name"].endswith("busy_share") else "higher")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # every step of the window is a mixed step: no `decode` record, so the readers of decode records stay off;
    # fetch_return_ms reads null everywhere; ops_bytes.py knows no latent attention
    for name in ("decode_step_ms", "decode_batch_mean", "experts_read_mean", "starved_slot_share", "fetch_return_ms",
                 "decode_hbm_roofline_share", "moe_decode_hbm_roofline_share", "tpot_p95_ms", "idle_unspanned_share"):
        assert CELL not in by_name[name]["workloads"], name
    # the cell is appended to the lists of the accepted readers its traced runs report, behind the cells the parent had
    accepted = [w["name"] for w in bench["workloads"][:index(bench["workloads"], CELL)]]
    for name in ("ttft_p50_ms.batch", "mixed_step_host_ms.batch", "attn_kernel_busy_share", "host_starved_share",
                 "idle_unexplained_share", "start_import_s", "start_engine_build_s", "moe_busy_share"):
        listed = by_name[name]["workloads"]
        assert all(listed.index(CELL) > listed.index(w) for w in accepted if w in listed), name
    mix = json.loads((BENCH / "traffic" / "longctx-closed.json").read_text())        # the accepted file, unedited
    assert (mix["loop"], mix["schedule_seed"], mix["ramp_s"], mix["max_total_tokens"]) == ("closed", 36, 8, 12700)
    clients = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"]
    assert clients == CONFIG["deployment_sizes"]["max_slots"] == 32                  # one client a slot


def test_byte_counts_by_hand():
    attention = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 32 * 128 * 3584          # int8
    assert (3584 * 768, 768 * 6144, 3584 * 576, 512 * 8192, 4096 * 3584) == (
        2_752_512, 4_718_592, 2_064_384, 4_194_304, 14_680_064)
    hyper = 2 * 14336 * 24 * 4
    dense, expert, router = 3 * 3584 * 9216, 3 * 3584 * 1024, 3584 * 64 * 2
    assert (attention, hyper, dense, expert, router) == (28_409_856, 2_752_512, 99_090_432, 11_010_048, 458_752)
    assert (mla_bytes.attention_bytes(CONFIG), mla_bytes.hyper_bytes(CONFIG), mla_bytes.expert_bytes(CONFIG)) == (
        attention, hyper, expert)
    dense_layer, expert_layer = attention + hyper + dense, attention + hyper + 65 * expert + router
    assert (dense_layer, expert_layer) == (130_252_800, 747_274_240)
    layers = 2 * dense_layer + 8 * expert_layer
    embed, head = 131072 * 3584 * 2, 131072 * 3584
    assert layers == 6_238_699_520 and all(f"{n:,}" in CONFIG["deployment"] for n in (
        attention, hyper, dense, dense_layer, expert, expert_layer, layers, embed, head))
    pool = 32 * 12800 * 10 * 640 * 2
    assert pool == 5_242_880_000 and f"{pool:,}" in CONFIG["deployment"] and "4,718,592,000" in CONFIG["deployment"]
    assert round((layers + embed + head + pool) / 1e9, 2) == 12.89 and "12.89 GB" in CONFIG["deployment"]
    fixed = mla_bytes.fixed_weight_bytes(CONFIG)
    assert fixed == 10 * (attention + hyper) + 2 * dense + 8 * (expert + router) + head == 1_071_316_992
    assert mla_bytes.latent_token_bytes(CONFIG) == 1152                                   # 576 values, not the 640 lanes
    # a mixed step: 55.2 distinct experts a layer, a 512-token chunk's 16 tiles at ~6k beside 16 one-query rows at ~8k
    record = {"kind": "mixed", "steps": 1, "experts_read": 55.2, "latent_pages_read": 17500.0,
              "latent_row_pages_read": 10000.0, "context_pages": 10500.0}
    want = fixed + 55.2 * 8 * expert + 17500 * 128 * 1152
    assert mla_bytes.step_bytes(CONFIG, record) == want == 8_513_834_188.8
    assert mla_bytes.step_bytes(CONFIG, {k: v for k, v in record.items() if k != "latent_row_pages_read"}) is None
    read = mla_bytes.row_read(CONFIG, [record, record])
    assert read == (2 * 10000 * 128 * 1152, 2 * 10000 * 128 * 32 * 2 * (2 * 512 + 64))
    assert read[1] / read[0] == 32 * 2 * 1088 / 1152 and read[1] / read[0] < 197e12 / 819e9   # under the ridge: the bytes bound it
    assert mla_bytes.row_read(CONFIG, [{"kind": "mixed"}]) is None and mla_bytes.row_read(CONFIG, []) is None


def test_the_four_readers_on_hand_built_sources():
    recs = [{"kind": "mixed", "steps": 1, "start_ts": 10.0 + i, "experts_read": 54.0 + i, "latent_pages_read": 17000.0 + 500 * i,
             "latent_row_pages_read": 9000.0 + 500 * i, "context_pages": 10000.0} for i in range(5)]
    recs.append({"kind": "mixed", "steps": 1, "start_ts": 15.0})                       # a record without the counts
    capture = {"capture_epoch_ns": [10.5e9, 13.5e9]}
    chip = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"busy_s": 2.9, "mixed_step_s": 0.034, "mla_decode_kernel": {"seconds": 0.007, "events": 30},
             "mla_attention_ops": {"seconds": 1.4, "events": 4000}, "mhc_ops": {"seconds": 0.11, "events": 9000}}
    src = {"config": CONFIG, "timeline": recs, "chip": chip, "status": {"jax_trace": capture}, "trace": trace}
    inside = recs[1:4]
    step = reader("mla_step_hbm_roofline_share")
    counts = sorted(mla_bytes.step_bytes(CONFIG, r) for r in inside)
    assert step.compute(src) == 100.0 * (counts[1] / 819e9) / 0.034 and 20.0 < step.compute(src) < 100.0
    attn = reader("mla_attn_roofline_share")
    tokens = sum(r["latent_row_pages_read"] for r in inside) * 128
    assert attn.compute(src) == 100.0 * (tokens * 1152 / 819e9) / 0.007 and 10.0 < attn.compute(src) < 100.0
    assert reader("mla_attn_busy_share").compute(src) == 100.0 * 1.4 / 2.9
    assert reader("mhc_busy_share").compute(src) == 100.0 * 0.11 / 2.9
    # nothing to read: no trace, another configuration, no capture window, a record without a counter, and
    # a trace that holds no such operation (the parent commit's program)
    other = json.loads((BENCH / "configs" / "trinity-mini-int8.json").read_text())
    empty = {"busy_s": 2.9, "mixed_step_s": 0.034, "mla_decode_kernel": {"seconds": 0.0, "events": 0},
             "mla_attention_ops": {"seconds": 0.0, "events": 0}, "mhc_ops": {"seconds": 0.0, "events": 0}}
    for name in NEW_READERS:
        r = reader(name)
        assert r.compute({**src, "trace": None}) is None and r.compute({**src, "config": other}) is None, name
        if name != "mla_step_hbm_roofline_share":
            assert r.compute({**src, "trace": empty}) is None, name
    assert step.compute({**src, "status": {}}) is None and attn.compute({**src, "status": {}}) is None
    assert step.compute({**src, "timeline": [recs[1], {**recs[5], "start_ts": 12.0}]}) is None
    assert attn.compute({**src, "timeline": [recs[1], {**recs[5], "start_ts": 12.0}]}) is None
    assert step.compute({**src, "trace": {"busy_s": 2.9}}) is None
    # what the patterns find in an instruction's text (events of the chip's trace, PR 47's runs)
    found = re.compile(mla_bytes.attention_pattern(CONFIG)).search
    assert found("%mla_chunk_attn_kernel.12 = bf16[576,32,512]{2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[32,100]{1,0} %b)")
    assert found("%mla_decode_attn_kernel.11 = bf16[32,32,512]{2,1,0} custom-call(s32[32,100]{1,0} %f)")
    assert found("%pad.614 = bf16[576,32,640]{2,1,0} pad(bf16[544,32,576]{2,1,0} %p, bf16[] %c)")
    assert found("%fusion.1093 = bf16[4096000,640]{1,0} fusion(bf16[4096000,640]{1,0} %g, s32[544]{0} %f)")
    assert not found("%fusion.1102 = bf16[544,3584]{1,0} fusion(s8[8,4096,3584]{2,1,0} %g, s32[] %i)")
    assert not found("%while.44 = (s32[], bf16[4,1,544,3584]{3,2,1,0}, bf16[4096000,640]{1,0}) while(%t)")
    one = re.compile(mla_bytes.kernel_pattern(mla_bytes.DECODE_KERNEL)).search
    assert one("%mla_decode_attn_kernel.12 = bf16[32,32,512] custom-call(%x)") and not one("%mla_chunk_attn_kernel.12 = x")
    found = re.compile(mla_bytes.hyper_pattern(CONFIG)).search
    assert found("%mhc_sinkhorn_kernel.3 = f32[16,8,128]{2,1,0} custom-call(f32[16,8,128]{2,1,0} %x)")
    assert found("%fusion.1073 = f32[24,544]{1,0} fusion(f32[4,3584,24]{1,0,2} %r, f32[544]{0} %a, bf16[4,1,544,3584] %x)")
    assert found("%fusion.7 = bf16[544,3584]{1,0} fusion(f32[4,544]{1,0} %pre, bf16[4,544,3584]{2,1,0} %x)")
    assert found("%fusion.8 = f32[4,4,32]{2,1,0} fusion(f32[16,32]{1,0} %x)")
    assert not found("%fusion.9 = bf16[544,3584]{1,0} fusion(bf16[544,9216]{1,0} %p0, s8[2,9216,3584] %p1)")
    assert not found("%while.44 = (s32[], bf16[4,1,544,3584]{3,2,1,0}, bf16[4096000,640]{1,0}) while(%t)")


def test_reference_child_on_probes_of_a_tiny_engine(tmp_path):
    """Probes of 85-225 prompt tokens served through mixed steps of 64 tokens
    (chunk tiles beside absorbed one-query rows) and decode chunks by the
    rehearsal's own engine; then ``reference.py --rehearse-cpu`` as ``run.py``
    starts it, on the file as it is: the UNEDITED child finds every leaf the
    reference declares in the program's two stacks. Then a control through the
    same child: the hyper-connections replaced by one plain stream, NOT ok."""
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    run_cfg = CONFIG["rehearsal"]
    args = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    engine = Engine(EngineConfig(
        model=run_cfg["preset"], quantize=args["--quantize"], dtype=args["--dtype"], attention=args["--attention"],
        page_size=int(args["--page-size"]), max_slots=int(args["--max-slots"]), max_seq_len=int(args["--max-seq-len"]),
        mixed_step=True, mixed_step_tokens=int(run_cfg["sidecar_env"]["SERVING_MIXED_STEP_TOKENS"])))
    assert engine.mixed_ok and engine.is_mla and set(engine.cache) == {"kv"}
    rng = np.random.default_rng(47)
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
    model = {**run_cfg["model"], "assumed_mechanisms": {"residual_mixing": False}}
    control_file = tmp_path / "control.json"
    control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**run_cfg, "model": model}}))
    out, control = child(control_file)
    assert out.returncode == 1 and control["ok"] is False, control
    assert control["mean_abs_diff"] > 5 * result["mean_abs_diff"] and control["max_abs_diff"] > 1.0, control
