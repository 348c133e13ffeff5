"""The LFM2-8B-A1B configuration's files (PR 43): the catalog row's keys
unchanged with ``reduced: []``, what was assumed stated, ``lfm2_bytes.py``'s
counts against arithmetic by hand, the two new readers on hand-built sources,
the entries found by name and after Trinity's, and the reference child on
probes of a tiny engine whose mixed steps cut convolution runs."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import numpy as np

import lfm2_bytes
import reference
from test_reference_child import serve_probe

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "lfm2-8b-a1b.reason"
CONFIG_FILE = BENCH / "configs" / "lfm2-8b-a1b-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
         "conv", "conv", "full_attention", "conv", "conv"]
# `config` of the catalog's row (architectures.jsonl, LFM2-8B-A1B), every key
SOURCE = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
          "layer_types": KINDS, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
          "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
          "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
          "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
          "vocab_size": 65536}
MECHANISMS = {"conv_lagged_taps": True, "conv_b_gate": True, "conv_c_gate": True, "selection_bias": "selection",
              "qk_norm": True, "conv_restart_every": 0}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_rows_keys_with_nothing_reduced_and_states_what_it_assumed():
    for key, value in SOURCE.items():
        assert CONFIG[key] == value and type(CONFIG[key]) is type(value), key
    assert CONFIG["reduced"] == [] and CONFIG["source"].endswith("LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert [i for i, k in enumerate(CONFIG["layer_types"]) if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    # the mechanisms config.json has no key for, and every size and scale this file set
    for said in ("qk_norm", "tie_word_embeddings", "intermediate_size", "head_dim", "conv", "state_dtype", "router",
                 "rope", "weights", "tokenizer", "context"):
        assert CONFIG["assumed"][said]
    assert CONFIG["tie_word_embeddings"] is True and CONFIG["assumed_mechanisms"] == MECHANISMS
    assert "WHOLE model" in CONFIG["deployment"] and "tensor_parallel 1" in CONFIG["deployment"]
    assert CONFIG["sidecar_args"] == ["--quantize", "int8", "--attention", "paged", "--page-size", "128",
                                      "--max-slots", "64", "--max-seq-len", "2048", "--dtype", "bfloat16"]
    sizes = CONFIG["deployment_sizes"]
    assert (sizes["decode_chunk"], sizes["state_dtype"], sizes["tensor_parallel"], sizes["max_slots"]) == (
        8, "float32", 1, 64)
    probes = CONFIG["probes"]
    assert probes["before_window_chars"] == [15] and probes["in_window_chars"] == [111, 239, 375, 1475]
    assert probes["max_tokens"] == 8 and (1475 + 25) // 512 == 2          # the last crosses two chunk boundaries
    tiny = CONFIG["rehearsal"]
    assert tiny["preset"] == "lfm2-test-tiny" and tiny["model"]["layer_types"] == KINDS[:8]
    ref = reference.load_reference(CONFIG["reference"])
    assert CONFIG["reference"] == "lfm2_moe"
    for model in (CONFIG, tiny["model"]):
        s = ref.sizes(model)
        assert s["layer_types"] == tuple(KINDS[:len(model["layer_types"])]) and s["selection_bias"] == "selection"
        assert (s["num_dense_layers"], s["conv_L_cache"], s["routed_scaling_factor"]) == (2, 3, 1)
        assert s["head_dim"] == (64 if model is CONFIG else 16) and s["rms_norm_eps"] == 1e-5
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == (
        "lfm2-8b-a1b", "int8", "bfloat16", "dense", 1)


def test_the_benchmark_gains_one_configuration_one_cell_and_two_readers_after_trinitys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # found by name, and after the entries the parent had: a later PR's appended entry must not fail this
    index = lambda entries, name: [e["name"] for e in entries].index(name)
    config = bench["configs"][index(bench["configs"], "lfm2-8b-a1b-int8")]
    assert index(bench["configs"], config["name"]) > index(bench["configs"], "trinity-mini-int8")
    assert config["reduced"] == CONFIG["reduced"] == [] and config["source"] == CONFIG["source"]
    assert config["file"] == "bench/configs/lfm2-8b-a1b-int8.json"
    assert index(bench["workloads"], CELL) > index(bench["workloads"], "trinity-mini.long-context")
    cell = bench["workloads"][index(bench["workloads"], CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "lfm2-8b-a1b-int8", "reason-closed", 1)
    first = index(bench["per_layer"], "lfm2_decode_hbm_roofline_share")
    assert first > index(bench["per_layer"], "afmoe_step_hbm_roofline_share")
    assert [m["name"] for m in bench["per_layer"][first:first + 2]] == [
        "lfm2_decode_hbm_roofline_share", "shortconv_busy_share"]
    for m in bench["per_layer"][first:first + 2]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # ops_bytes.py would read the dense width 7,168 as the experts' and count 24 alike layers; moe_bytes.py holds
    # SmallThinker's constants; fetch_return_ms reads null everywhere; tpot_p95_ms wants 200 requests a capture
    for name in ("decode_hbm_roofline_share", "moe_decode_hbm_roofline_share", "fetch_return_ms", "tpot_p95_ms"):
        assert CELL not in by_name[name]["workloads"]
    # the cell is appended to the lists of the accepted readers it feeds, behind the cells the parent had
    accepted = [w["name"] for w in bench["workloads"][:index(bench["workloads"], CELL)]]
    for name in ("decode_batch_mean", "decode_step_ms", "experts_read_mean", "ttft_p50_ms.batch",
                 "mixed_step_host_ms.batch", "host_starved_share", "idle_unexplained_share", "idle_unspanned_share",
                 "starved_slot_share", "starved_delivery_share", "starved_unspanned_share", "start_import_s",
                 "start_engine_build_s", "moe_busy_share", "attn_kernel_busy_share"):
        listed = by_name[name]["workloads"]
        assert all(listed.index(CELL) > listed.index(w) for w in accepted if w in listed), name
    mix = json.loads((BENCH / "traffic" / "reason-closed.json").read_text())        # the accepted file, unedited
    assert (mix["loop"], mix["schedule_seed"], mix["ramp_s"], mix["max_total_tokens"]) == ("closed", 32, 8, 1975)
    clients = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"]
    assert clients == CONFIG["deployment_sizes"]["max_slots"] == 64                  # one client a slot


def test_byte_counts_by_hand():
    conv = 2048 * 6144 + 2048 * 2048                                               # int8: W_in, W_out
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512                                   # q and o; k and v
    dense = 3 * 2048 * 7168
    expert = 3 * 2048 * 1792
    assert (conv, attention, dense, expert, 32 * expert) == (16_777_216, 10_485_760, 44_040_192, 11_010_048, 352_321_536)
    assert (lfm2_bytes.conv_bytes(CONFIG), lfm2_bytes.attention_bytes(CONFIG), lfm2_bytes.expert_bytes(CONFIG)) == (
        conv, attention, expert)
    layers = 18 * conv + 6 * attention + 2 * dense + 22 * 32 * expert
    assert layers == 8_204_058_624 and "8,204,058,624" in CONFIG["deployment"]
    routers, head = 22 * 2048 * 32 * 2, 65536 * 2048 * 2                           # bf16; the tied head as held
    assert (routers, head) == (2_883_584, 268_435_456) and "268,435,456" in CONFIG["deployment"]
    fixed = lfm2_bytes.fixed_weight_bytes(CONFIG)
    assert fixed == 18 * conv + 6 * attention + 2 * dense + routers + head == 724_303_872
    page = lfm2_bytes.page_bytes(CONFIG)
    assert page == 128 * 2 * 8 * 64 * 2 == 262_144
    pool = 64 * 2048 * 6 * (page // 128)
    assert pool == 1_610_612_736 and "1,610,612,736" in CONFIG["deployment"]
    state = lfm2_bytes.state_bytes_per_row(CONFIG)
    assert state == 18 * 2 * 2048 * 4 == 294_912 and 64 * state == 18_874_368
    assert round((layers + head + pool + 64 * state) / 1e9, 1) == 10.1 and "10.1 GB" in CONFIG["deployment"]
    s = lfm2_bytes.sizes(CONFIG)
    assert (s["conv"], s["full"], s["dense_layers"], s["E"], s["K"], s["slots"]) == (18, 6, 2, 32, 3, 64)
    # a chunk of 8 steps whose 64 rows chose 31.5 distinct experts a layer and hold 2,304 pages a step over the
    # 6 caching layers (6 pages a row a layer: ~700 tokens)
    record = {"kind": "decode", "steps": 8, "batch": 64, "experts_read": 31.5, "context_pages": 8 * 2304.0}
    want = fixed + 31.5 * 22 * expert + 2304 * page + 64 * 2 * state
    assert lfm2_bytes.decode_step_bytes(CONFIG, record) == want == 8_995_995_648.0
    assert lfm2_bytes.decode_step_bytes(CONFIG, {"kind": "decode", "steps": 8, "batch": 64, "experts_read": 31.5}) is None


def test_the_two_readers_on_hand_built_sources():
    recs = [{"kind": "decode", "steps": 8, "batch": 64, "start_ts": 10.0, "experts_read": 32.0, "context_pages": 20000.0},
            {"kind": "decode", "steps": 8, "batch": 64, "start_ts": 11.0, "experts_read": 31.0, "context_pages": 18000.0},
            {"kind": "decode", "steps": 8, "batch": 63, "start_ts": 12.0, "experts_read": 31.5, "context_pages": 19000.0},
            {"kind": "mixed", "steps": 1, "batch": 64, "start_ts": 12.5, "experts_read": 32.0, "context_pages": 2400.0},
            {"kind": "decode", "steps": 8, "batch": 64, "start_ts": 13.0, "experts_read": 31.9, "context_pages": 18500.0},
            {"kind": "decode", "steps": 8, "batch": 64, "start_ts": 14.0}]           # a record without the counts
    capture = {"capture_epoch_ns": [10.5e9, 13.5e9]}
    src = {"config": CONFIG, "timeline": recs, "chip": {"hbm_bytes_per_s": 819e9},
           "status": {"jax_trace": capture}, "trace": {"busy_s": 2.0, "decode_chunk_s": 0.112,
                                                        "shortconv_ops": {"shortconv_s": 0.17, "shortconv_events": 900}}}
    roof = reader("lfm2_decode_hbm_roofline_share")
    counts = sorted(lfm2_bytes.decode_step_bytes(CONFIG, r) for r in (recs[1], recs[2], recs[4]))
    assert roof.compute(src) == 100.0 * (counts[1] / 819e9) / (0.112 / 8)          # the median step's bytes
    assert 40.0 < roof.compute(src) < 100.0
    # nothing to read: no trace, no decode program in it, no capture window, a record without a counter, no decode
    # record in the capture, another configuration
    assert roof.compute({**src, "trace": None}) is None
    assert roof.compute({**src, "trace": {"busy_s": 2.0}}) is None
    assert roof.compute({**src, "status": {}}) is None
    assert roof.compute({**src, "timeline": [recs[1], {**recs[5], "start_ts": 12.0}]}) is None
    assert roof.compute({**src, "timeline": recs[3:4]}) is None
    other = json.loads((BENCH / "configs" / "trinity-mini-int8.json").read_text())
    assert roof.compute({**src, "config": other}) is None
    busy = reader("shortconv_busy_share")
    assert busy.compute(src) == 100.0 * 0.17 / 2.0
    # nothing to read: no trace, no busy time, no such operation in it (the parent commit's program), another configuration
    assert busy.compute({**src, "trace": None}) is None and busy.compute({**src, "trace": {"busy_s": 0.0}}) is None
    none = {"busy_s": 2.0, "shortconv_ops": {"shortconv_s": 0.0, "shortconv_events": 0}}
    assert busy.compute({**src, "trace": none}) is None and busy.compute({**src, "trace": {"busy_s": 2.0}}) is None
    assert busy.compute({**src, "config": other}) is None
    # what the pattern finds in an instruction's text: the input projection's products, the store's shapes, a
    # kernel by name; not the output projection's fusion (it has the shape of the attention's q and o)
    import re
    found = re.compile(lfm2_bytes.shortconv_pattern(CONFIG)).search
    assert found("%fusion.12 = bf16[64,6144]{1,0} fusion(%p0, %p1), kind=kOutput")
    assert found("%fusion.7 = f32[64,2,2048]{2,1,0} fusion(%p), kind=kLoop")
    assert found("%dynamic-update-slice.3 = f32[18,64,2,2048]{3,2,1,0} dynamic-update-slice(%a, %b, %i)")
    assert found("%shortconv_decode.1 = f32[64,2048] custom-call(%x)")
    assert not found("%fusion.9 = bf16[64,2048]{1,0} fusion(%p0, s8[6,2048,2048] %p1), kind=kOutput")
    # nor a loop or a branch that carries the store (its event spans everything inside it)
    assert not found("%while.84 = (s32[], bf16[64,1,2048], f32[18,64,2,2048]{3,2,1,0}) while(%tuple.1)")
    assert not found("%cond.9 = (bf16[64,1,2048], f32[18,64,2,2048]{3,2,1,0}) conditional(%p, %a, %b)")
    assert not found("%fusion.3 = bf16[64,7168]{1,0} fusion(%p0, %p1), kind=kOutput")


def test_reference_child_on_probes_that_cross_chunk_boundaries_of_a_tiny_engine(tmp_path):
    """Probes of 85-225 prompt tokens served through mixed steps of 64 tokens
    (every chunk boundary falls inside a convolution run) and decode chunks by
    the rehearsal's own engine; then ``reference.py --rehearse-cpu`` as
    ``run.py`` starts it, on the file as it is: the UNEDITED child finds every
    leaf the reference declares in the program's four stacks and reads the
    right one at each index."""
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    run_cfg = CONFIG["rehearsal"]
    args = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    engine = Engine(EngineConfig(
        model=run_cfg["preset"], quantize=args["--quantize"], dtype=args["--dtype"], attention=args["--attention"],
        page_size=int(args["--page-size"]), max_slots=int(args["--max-slots"]), max_seq_len=int(args["--max-seq-len"]),
        mixed_step=True, mixed_step_tokens=int(run_cfg["sidecar_env"]["SERVING_MIXED_STEP_TOKENS"])))
    assert engine.mixed_ok and engine.carries_state
    rng = np.random.default_rng(43)
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
    assert result["ok"], result
    assert out.returncode == (0 if result["ok"] else 1)
    # A control through the same child: the file with the convolution's lagged taps zeroed (a program whose
    # carried tail were lost or never read would compute that). NOT ok by both limits.
    model = {**run_cfg["model"], "assumed_mechanisms": {**MECHANISMS, "conv_lagged_taps": False}}
    control_file = tmp_path / "control.json"
    control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**run_cfg, "model": model}}))
    out, control = child(control_file)
    assert out.returncode == 1 and control["ok"] is False, control
    assert control["mean_abs_diff"] > 5 * result["mean_abs_diff"] and control["max_abs_diff"] > 1.0, control
