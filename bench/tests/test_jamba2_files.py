"""The jamba2-3b configuration's files (PR 64): every key of the catalog row
unchanged and ``reduced`` empty, what was assumed stated, ``mamba1_bytes.py``'s
counts against arithmetic by hand, the three new readers on hand-built sources,
the entries found by name and after EvaByte's, and the reference child on probes
of a tiny engine whose mixed steps hand every mixer's state over chunk boundaries."""
import importlib.util
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np

import mamba1_bytes
from test_reference_child import serve_probe

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "jamba2-3b.long-context"
CONFIG_FILE = BENCH / "configs" / "jamba2-3b-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
# `config` of the catalog's row (architectures.jsonl, AI21-Jamba2-3B), every key
SOURCE = {"attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2,
          "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
          "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
          "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
          "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
          "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
          "vocab_size": 65536}
NEW_READERS = ["selscan_busy_share", "selscan_chunk_roofline_share", "jamba2_mixed_step_mfu"]
APPENDED = ["ttft_p50_ms.batch", "attn_kernel_busy_share", "mixed_step_host_ms.batch", "host_starved_share",
            "idle_unexplained_share", "start_import_s", "start_engine_build_s", "post_warmup_compile_s",
            "kv_write_busy_share", "scope_attention_share", "scope_state_share", "scope_head_sampling_share",
            "scope_unscoped_share", "scope_mlp_share"]


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_every_key_of_the_row_and_states_what_it_assumed():
    for key, value in SOURCE.items():
        assert CONFIG[key] == value and type(CONFIG[key]) is type(value), key
    assert CONFIG["reduced"] == [] and CONFIG["source"].endswith("ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    for said in ("layer_order", "head_dim", "attention", "mixer", "inner_norms", "norms", "mlp", "mamba_init",
                 "quantisation", "seeding", "tokenizer", "context", "chunk"):
        assert CONFIG["assumed"][said], said
    assert CONFIG["assumed_mechanisms"] == {"inner_norms": True, "state_dtype": "float32", "state_from_previous": False,
                                            "state_restart_every": 0}
    assert "all 28 layers" in CONFIG["deployment"] and "tensor_parallel 1" in CONFIG["deployment"]
    assert CONFIG["sidecar_args"] == ["--max-slots", "32", "--max-seq-len", "12800", "--page-size", "128",
                                      "--quantize", "int8", "--attention", "paged", "--dtype", "bfloat16"]
    assert CONFIG["preset"] == "jamba2-3b" and CONFIG["reference"] == "mamba1_mqa"
    assert CONFIG["deployment_sizes"]["state_dtype"] == "float32" and CONFIG["deployment_sizes"]["mixed_step_tokens"] == 544
    assert CONFIG["probes"]["before_window_chars"] == [3000] and CONFIG["probes"]["in_window_chars"] == [111, 1500, 2500, 5000, 9000]
    # the program's preset is the file's sizes
    sys.path.insert(0, str(ROOT))
    from inference_gateway_tpu.models import jamba

    cfg = jamba.PRESETS[CONFIG["preset"]]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size, cfg.vocab_size) == (
        2560, 28, 20, 1, 8192, 65536)
    assert (cfg.attn_layer_offset, cfg.attn_layer_period, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_expand,
            cfg.mamba_dt_rank, cfg.rms_norm_eps, cfg.tie_word_embeddings, cfg.max_position_embeddings) == (
        7, 14, 16, 4, 2, 160, 1e-6, True, 262144)
    assert 32 * cfg.state_bytes_per_slot == 323_747_840 and "323,747,840" in CONFIG["deployment"]


def test_the_benchmark_gains_one_configuration_one_cell_and_three_readers_after_evabytes():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["configs"][-1]["name"] == "jamba2-3b-int8" and bench["configs"][-2]["name"] == "evabyte-6.5b-int8"
    assert bench["configs"][-1]["reduced"] == [] and bench["configs"][-1]["source"] == CONFIG["source"]
    assert bench["workloads"][-1] == {**bench["workloads"][-1], "name": CELL, "config": "jamba2-3b-int8",
                                      "traffic": "longctx-closed", "chips": 1}
    assert json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"] == 32
    assert [m["name"] for m in bench["per_layer"][-3:]] == NEW_READERS
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s" and m["unit"] == "%" and m["source"] == "device_trace"
        r = reader(m["name"])
        assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (m["unit"], m["layer"], m["moves"], m["source"])
    lists = {m["name"]: m["workloads"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert sorted(lists) == sorted(APPENDED + NEW_READERS) and all(w[-1] == CELL for w in lists.values())
    assert CELL not in next(m for m in bench["per_layer"] if m["name"] == "fetch_return_ms")["workloads"]
    assert len(json.dumps(bench)) < 64 << 10 and len(bench["workloads"][-1]["why"]) <= 200


def test_byte_counts_by_hand():
    s = mamba1_bytes.sizes(CONFIG)
    assert (s["mamba"], s["attention"], s["Di"], s["N"], s["R"], s["Q"], s["tokens"]) == (26, 2, 5120, 16, 160, 64, 544)
    assert mamba1_bytes.state_bytes_per_row_layer(CONFIG) == 16 * 5120 * 4 == 327_680
    assert mamba1_bytes.token_bytes(CONFIG) == (3 * 5120 + 2 * 16) * 4 == 61_568   # B and C as values, not as the columns handed in
    assert mamba1_bytes.token_ops(CONFIG) == 9 * 5120 * 16 == 737_280
    mixer = 2 * 2560 * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120 + 2 * 5120 * 2560
    attention = 2 * 2560 * (2 * 2560 + 2 * 128)
    assert mamba1_bytes.token_flops(CONFIG) == 26 * mixer + 2 * attention + 28 * 3 * 2 * 2560 * 8192 == 5_716_705_280
    rec = {"kind": "mixed", "ssm_chunk_tokens": 512.0 * 26, "ssm_chunk_rows": 26.0, "state_rows": 20.0,
           "attn_wide_tokens": 512, "context_tokens": 150_000, "batch": 21}
    assert mamba1_bytes.chunk_work(CONFIG, [rec]) == (512 * 26 * 737_280, 512 * 26 * 61_568 + 26 * 2 * 327_680)
    assert mamba1_bytes.mixed_step_flops(CONFIG, rec) == (
        532 * 5_716_705_280 + 2 * 4 * 20 * 128 * 150_000 + 21 * 2 * 2560 * 65536)
    assert mamba1_bytes.chunk_work(CONFIG, [{"kind": "mixed"}]) is None and mamba1_bytes.chunk_work(CONFIG, []) is None
    assert mamba1_bytes.mixed_step_flops(CONFIG, {**rec, "state_rows": None}) is None
    assert mamba1_bytes.is_jamba(CONFIG) and not mamba1_bytes.is_jamba({"model_type": "granitemoehybrid"})


def test_the_three_readers_on_hand_built_sources():
    recs = [{"kind": "mixed", "steps": 1, "start_ts": 10.0 + i, "batch": 21, "ssm_chunk_tokens": 512.0 * 26,
             "ssm_chunk_rows": 26.0, "state_rows": 20.0 + i, "attn_wide_tokens": 512, "context_tokens": 150_000 + 1000 * i}
            for i in range(5)]
    recs.append({"kind": "mixed", "steps": 1, "start_ts": 15.0})                       # a record without the counts
    capture = {"capture_epoch_ns": [10.5e9, 13.5e9]}
    chip = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"busy_s": 2.9, "mixed_step_s": 0.03, "selscan_ops": {"seconds": 0.6, "events": 9000},
             "ssm_scan_chunk_kernel": {"seconds": 0.012, "events": 78}}
    src = {"config": CONFIG, "timeline": recs, "chip": chip, "status": {"jax_trace": capture}, "trace": trace}
    inside = recs[1:4]
    assert reader("selscan_busy_share").compute(src) == 100.0 * 0.6 / 2.9
    chunk = reader("selscan_chunk_roofline_share")
    ops, moved = 3 * 512 * 26 * 737_280, 3 * (512 * 26 * 61_568 + 26 * 2 * 327_680)
    assert moved / 819e9 > ops / 197e12        # the bytes' bound is the greater: the body is no matrix product
    assert chunk.compute(src) == 100.0 * (moved / 819e9) / 0.012 and 5.0 < chunk.compute(src) < 100.0
    mfu = reader("jamba2_mixed_step_mfu")
    counts = sorted(mamba1_bytes.mixed_step_flops(CONFIG, r) for r in inside)
    assert mfu.compute(src) == 100.0 * (counts[1] / 197e12) / 0.03 and 10.0 < mfu.compute(src) < 100.0
    # nothing to read: no trace, another configuration, no capture window, a record without a counter, and
    # a trace that holds no such operation (the parent commit's program)
    other = json.loads((BENCH / "configs" / "granite-4.0-h-small-int8.json").read_text())
    empty = {"busy_s": 2.9, "mixed_step_s": 0.03, "selscan_ops": {"seconds": 0.0, "events": 0},
             "ssm_scan_chunk_kernel": {"seconds": 0.0, "events": 0}}
    for name in NEW_READERS:
        r = reader(name)
        assert r.compute({**src, "trace": None}) is None and r.compute({**src, "config": other}) is None, name
        if name != "jamba2_mixed_step_mfu":
            assert r.compute({**src, "trace": empty}) is None, name
    for r in (chunk, mfu):
        assert r.compute({**src, "status": {}}) is None
        assert r.compute({**src, "timeline": [recs[1], {**recs[5], "start_ts": 12.0}]}) is None
    assert mfu.compute({**src, "trace": {"busy_s": 2.9}}) is None
    # what the patterns find in an instruction's text
    from mla_bytes import kernel_pattern

    one = re.compile(kernel_pattern(mamba1_bytes.CHUNK_KERNEL)).search
    assert one("%ssm_scan_chunk_kernel.10 = (f32[576,5120]{1,0:T(8,128)}, f32[26,32,16,5120]{3,2,1,0}) custom-call(%a)")
    assert not one("%fusion.3 = f32[544,5120]{1,0} fusion(%ssm_scan_chunk_kernel.10)")
    # the state-space operations: what ``ssm_busy_share``'s pattern finds for the other Mamba-1 model, the state laid (N, Di)
    found = re.compile(mamba1_bytes.scan_pattern(CONFIG)).search
    assert found("%ssm_scan_chunk_kernel.11 = (f32[576,5120]{1,0}, f32[26,32,16,5120]{3,2,1,0}) custom-call(%a)")
    assert found("%ssm_scan_decode_kernel.3 = (f32[32,40,128]{2,1,0}, f32[26,32,16,5120]{3,2,1,0}) custom-call(%a)")
    assert found("%fusion.500 = f32[32,16,5120]{2,1,0:T(8,128)} fusion(%get-tuple-element.3441)")
    assert found("%fusion.12 = f32[16,5120]{1,0} fusion(%p)") and found("%fusion.7 = f32[32,4,5120]{2,1,0} fusion(%p)")
    assert found("%fusion.8 = f32[32,5120]{1,0} fusion(f32[32,3,5120]{2,1,0} %p0, bf16[32,5120]{1,0} %p1)")
    assert not found("%while.59 = (s32[], bf16[1,544,2560], f32[26,32,3,5120], f32[26,32,16,5120]) while(%t)")
    assert not found("%fusion.640 = f32[544,5120]{1,0:T(8,128)} fusion(%p)") and not found("%fusion.641 = f32[32,5120]{1,0} fusion(%p)")
    assert not found("%fusion.9 = bf16[544,10240]{1,0} fusion(bf16[544,2560]{1,0} %p0, s8[26,2560,10240] %p1)")
    hybrid = json.loads((BENCH / "configs" / "phi-4-mini-flash-int8.json").read_text())
    import hybrid_bytes

    theirs = hybrid_bytes.ssm_pattern(hybrid).replace("64,5120,16", "32,16,5120").replace("5120,16", "16,5120").replace("64,", "32,")
    assert theirs == mamba1_bytes.scan_pattern(CONFIG)       # one pattern, but for the slots and the state's layout


def test_reference_child_on_probes_of_a_tiny_engine(tmp_path):
    """Probes of 85-225 prompt tokens served through mixed steps of 64 tokens
    (each probe's states handed over chunk boundaries beside one-token rows) and
    decode chunks by the rehearsal's own engine; then ``reference.py
    --rehearse-cpu`` as ``run.py`` starts it, on the file as it is: the UNEDITED
    child finds every leaf the reference declares in the program's three stacks.
    Then a control through the same child: the inner norms left out, NOT ok."""
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    run_cfg = CONFIG["rehearsal"]
    args = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    engine = Engine(EngineConfig(
        model=run_cfg["preset"], quantize=args["--quantize"], dtype=args["--dtype"], attention=args["--attention"],
        page_size=int(args["--page-size"]), max_slots=int(args["--max-slots"]), max_seq_len=int(args["--max-seq-len"]),
        mixed_step=True, mixed_step_tokens=int(run_cfg["sidecar_env"]["SERVING_MIXED_STEP_TOKENS"])))
    assert engine.mixed_ok and engine.carries_state and set(engine.cache) == {"k", "v", "conv", "ssm"}
    rng = np.random.default_rng(64)
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
    model = {**run_cfg["model"], "assumed_mechanisms": {**run_cfg["model"]["assumed_mechanisms"], "inner_norms": False}}
    control_file = tmp_path / "control.json"
    control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**run_cfg, "model": model}}))
    out, control = child(control_file)
    assert out.returncode == 1 and control["ok"] is False, control
    assert control["mean_abs_diff"] > 5 * result["mean_abs_diff"] and control["max_abs_diff"] > 1.0, control
