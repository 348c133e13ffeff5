"""The Kimi-Linear configuration's files (PR 54): the catalog row's keys unchanged
but the three in ``reduced``, what was assumed stated, ``kda_bytes.py``'s counts
against arithmetic by hand, the four new readers on hand-built sources, the
entries found by name and after Granite's, and the reference child on probes of a
tiny engine whose mixed steps hand every KDA state over block and chunk
boundaries, with the four controls through the same child."""
import importlib.util
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np

import kda_bytes
import reference
from test_reference_child import serve_probe

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "kimi-linear-48b-a3b.long-context"
CONFIG_FILE = BENCH / "configs" / "kimi-linear-48b-a3b-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
# `config` of the catalog's row (architectures.jsonl, Kimi-Linear-48B-A3B-Instruct), every key
SOURCE = {"first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216,
          "kv_lora_rank": 512,
          "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                                 "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
                                 "num_heads": 32, "short_conv_kernel_size": 4},
          "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
          "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
          "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
          "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
          "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
          "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
          "v_head_dim": 128, "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 8,
           "linear_attn_config": {**SOURCE["linear_attn_config"], "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8]},
           "num_experts": 128}
NEW_READERS = ["kda_busy_share", "kda_chunk_roofline_share", "kda_state_hbm_roofline_share", "kimi_mixed_step_mfu"]
MECHANISMS = {"delta_rule": True, "decay_per_channel": True, "state_restart_every": 0, "absent_experts": "left_out"}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_rows_keys_but_the_three_reduced_and_states_what_it_assumed():
    for key, value in SOURCE.items():
        want = REDUCED.get(key, value)
        assert CONFIG[key] == want and type(CONFIG[key]) is type(want), key
    lin = CONFIG["linear_attn_config"]         # a width inside the reduced group is as published
    assert (lin["head_dim"], lin["num_heads"], lin["short_conv_kernel_size"]) == (128, 32, 4)
    assert [l for l in SOURCE["linear_attn_config"]["kda_layers"] if l <= 8] == lin["kda_layers"]
    assert [l for l in SOURCE["linear_attn_config"]["full_attn_layers"] if l <= 8] == lin["full_attn_layers"]
    assert CONFIG["reduced"] == list(REDUCED) and set(CONFIG["published"]) >= set(REDUCED)
    assert CONFIG["source"].endswith("moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert (CONFIG["routed_experts"], CONFIG["num_local_experts"], CONFIG["num_experts_per_tok"]) == (256, 128, 8)
    for said in ("kda_projections", "short_convolution", "qk_norm", "decay", "beta", "delta_rule", "output_gate", "kda_init",
                 "mla", "head_dim", "router", "ffn", "block", "quantisation", "seeding"):
        assert CONFIG["assumed"][said], said
    assert CONFIG["assumed_mechanisms"] == MECHANISMS
    assert "ONE of the two chips that share each layer of the first of four pipeline stages" in CONFIG["deployment"]
    assert CONFIG["sidecar_args"] == ["--max-slots", "32", "--max-seq-len", "12800", "--page-size", "128",
                                      "--quantize", "int8", "--attention", "paged", "--dtype", "bfloat16"]
    probes = CONFIG["probes"]
    assert probes["before_window_chars"] == [3000] and probes["in_window_chars"] == [1500, 2500, 5000, 9000]
    tiny = CONFIG["rehearsal"]
    assert tiny["preset"] == "kimi-linear-test-tiny" and CONFIG["preset"] == "kimi-linear-48b-a3b-stage8"
    ref = reference.load_reference(CONFIG["reference"])
    assert CONFIG["reference"] == "kda_mla_moe" and CONFIG["deployment_sizes"]["state_dtype"] == "float32"
    for model in (CONFIG, tiny["model"]):
        s = ref.sizes(model)
        assert (s["kda_layers"], s["full_attn_layers"], s["kda_conv"], s["first_k_dense_replace"]) == ((1, 2, 3, 5, 6, 7), (4, 8), 4, 1)
        assert s["delta_rule"] is True and s["decay_per_channel"] is True and s["absent_experts"] == "left_out"
        assert s["num_experts"] * 2 == s["routed_experts"] and s["routed_scaling_factor"] == 2.446
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == (
        "kimi-linear-48b-a3b-stage8", "int8", "bfloat16", "dense", 1)
    # the program's preset holds the file's sizes
    sys.path.insert(0, str(ROOT))
    from inference_gateway_tpu.models import mixtral

    for preset, model in ((CONFIG["preset"], CONFIG), (tiny["preset"], tiny["model"])):
        p, lin = mixtral.PRESETS[preset], model["linear_attn_config"]
        assert [i + 1 for i, f in enumerate(p.layer_conv) if f == 3] == lin["kda_layers"]
        assert [i + 1 for i, f in enumerate(p.layer_conv) if f == 0] == lin["full_attn_layers"]
        assert (p.hidden_size, p.num_heads, p.vocab_size, p.dense_intermediate_size, p.intermediate_size, p.num_experts,
                p.held_experts, p.experts_per_token, p.num_dense_layers, p.shared_experts, p.route_scale) == tuple(model[k] for k in (
                    "hidden_size", "num_attention_heads", "vocab_size", "intermediate_size", "moe_intermediate_size",
                    "routed_experts", "num_experts", "num_experts_per_token", "first_k_dense_replace", "num_shared_experts",
                    "routed_scaling_factor"))
        assert (p.kda_heads, p.kda_head_dim, p.conv_kernel) == (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
        assert (p.kv_lora_rank, p.q_lora_rank, p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim, p.rms_norm_eps) == (
            model["kv_lora_rank"], 0, model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"], model["rms_norm_eps"])
    assert mixtral.PRESETS[CONFIG["preset"]].kda_block == CONFIG["deployment_sizes"]["kda_block"] == 64
    assert mixtral.PRESETS[tiny["preset"]].kda_block == tiny["deployment_sizes"]["kda_block"] == 8


def test_the_benchmark_gains_one_configuration_one_cell_and_four_readers_after_granites():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # found by name, and after the entries the parent had: a later PR's appended entry must not fail this
    index = lambda entries, name: [e["name"] for e in entries].index(name)
    config = bench["configs"][index(bench["configs"], "kimi-linear-48b-a3b-int8")]
    assert index(bench["configs"], config["name"]) > index(bench["configs"], "granite-4.0-h-small-int8")
    assert config["reduced"] == CONFIG["reduced"] == ["num_hidden_layers", "linear_attn_config", "num_experts"]
    assert config["source"] == CONFIG["source"] and config["file"] == "bench/configs/kimi-linear-48b-a3b-int8.json"
    assert index(bench["workloads"], CELL) > index(bench["workloads"], "granite-4.0-h-small.long-context")
    cell = bench["workloads"][index(bench["workloads"], CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, "kimi-linear-48b-a3b-int8", "longctx-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    first = index(bench["per_layer"], NEW_READERS[0])
    assert first > index(bench["per_layer"], "scope_unscoped_share")
    assert [m["name"] for m in bench["per_layer"][first:first + 4]] == NEW_READERS
    for m in bench["per_layer"][first:first + 4]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert m["better"] == ("lower" if m["name"].endswith("busy_share") else "higher")
        assert m["layer"] in {o["layer"] for o in bench["per_layer"][:first]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # every step of the window is a mixed step: the readers of decode records stay off; the three mla_* readers answer
    # for Xing alone (mla_bytes.is_xing) and Granite's four for Granite alone: a listed cell that reads nothing earns a note
    for name in ("decode_step_ms", "decode_batch_mean", "experts_read_mean", "starved_slot_share", "fetch_return_ms",
                 "decode_hbm_roofline_share", "moe_decode_hbm_roofline_share", "tpot_p95_ms", "idle_unspanned_share",
                 "ssm_busy_share", "hybrid_decode_hbm_roofline_share", "window_skip_share", "shortconv_busy_share",
                 "mla_attn_busy_share", "mla_attn_roofline_share", "mla_step_hbm_roofline_share", "mhc_busy_share",
                 "ssd_busy_share", "ssd_chunk_roofline_share", "ssd_state_hbm_roofline_share", "granite_mixed_step_mfu"):
        assert CELL not in by_name[name]["workloads"], name
    # the cell is appended to the lists of the accepted readers its traced runs report, behind the cells the parent had
    accepted = [w["name"] for w in bench["workloads"][:index(bench["workloads"], CELL)]]
    for name in ("ttft_p50_ms.batch", "mixed_step_host_ms.batch", "attn_kernel_busy_share", "host_starved_share",
                 "idle_unexplained_share", "start_import_s", "start_engine_build_s", "moe_busy_share",
                 "post_warmup_compile_s", "scope_attention_share", "scope_mlp_share", "scope_moe_share",
                 "scope_state_share", "scope_head_sampling_share", "scope_unscoped_share"):
        listed = by_name[name]["workloads"]
        assert all(listed.index(CELL) > listed.index(w) for w in accepted if w in listed), name
    mix = json.loads((BENCH / "traffic" / "longctx-closed.json").read_text())        # the accepted file, unedited
    assert (mix["loop"], mix["schedule_seed"], mix["ramp_s"], mix["max_total_tokens"]) == ("closed", 36, 8, 12700)
    clients = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"]
    assert clients == CONFIG["deployment_sizes"]["max_slots"] == 32                  # one client a slot


def test_byte_counts_by_hand():
    qkv, out, pairs = 2304 * 12288, 4096 * 2304, 2 * (2304 * 128 + 128 * 4096)                          # int8
    wq, wdkv, wukv, wo = 2304 * 32 * 192, 2304 * 576, 512 * 32 * 256, 4096 * 2304
    experts, shared, router, dense = 128 * 3 * 2304 * 1024, 3 * 2304 * 1024, 2304 * 256 * 2, 3 * 2304 * 9216
    assert (qkv, out, pairs, wq, wdkv, wukv, wo) == (28_311_552, 9_437_184, 1_638_400, 14_155_776, 1_327_104, 4_194_304, 9_437_184)
    assert (experts, shared, router, dense) == (905_969_664, 7_077_888, 1_179_648, 63_700_992)
    embed, head = 163840 * 2304 * 2, 163840 * 2304
    state, tails = 32 * 128 * 128 * 4, 3 * 12288 * 4
    slot = 6 * (state + tails)
    pool = 2 * 32 * 12800 * 640 * 2
    assert (state, state + tails, 32 * slot, pool) == (2_097_152, 2_244_608, 430_964_736, 1_048_576_000)
    assert all(f"{n:,}" in CONFIG["deployment"] for n in (
        qkv, out, pairs, wq, wdkv, wukv, wo, experts, shared, router, dense, embed, head, state, state + tails, 32 * slot, pool))
    kda_layer, mla_layer = qkv + out + pairs + experts + shared + router, wq + wdkv + wukv + wo + experts + shared + router
    assert round(kda_layer / 1e6) == 954 and round(mla_layer / 1e6) == 943 and "7,909,210,880 B" in CONFIG["deployment"]
    assert round((7_909_210_880 + 32 * slot + pool) / 1e9, 1) == 9.4 and "plan 9.4 GB" in CONFIG["deployment"]
    assert kda_bytes.state_bytes_per_row_layer(CONFIG) == state
    s = kda_bytes.sizes(CONFIG)
    assert (s["kda"], s["mla"], s["P"], s["Q"], s["slots"], s["tokens"], s["held"], s["routed"]) == (6, 2, 4096, 64, 32, 544, 128, 256)
    # a visit of a block of 64 tokens, a head: three products with the state, the pairs below the diagonal four times
    assert kda_bytes.block_flops(CONFIG) == 32 * (3 * 2 * 64 * 128 * 128 + 4 * 64 * 64 * 128) == 268_435_456
    assert kda_bytes.block_bytes(CONFIG) == 5 * 64 * 4096 * 4 == 5_242_880
    assert kda_bytes.block_flops(CONFIG) / kda_bytes.block_bytes(CONFIG) < 197e12 / 819e9               # under the ridge
    mixer = 2 * (qkv + out + pairs) + 2 * 2304 * 32
    latent = 2 * (wq + wdkv) + 2 * 32 * 512 * 256 + 2 * wo
    ffn = 2 * dense + 7 * 2 * shared
    assert kda_bytes.token_flops(CONFIG) == 6 * mixer + 2 * latent + ffn == 816_480_256
    # a mixed step: a 512-token chunk behind 15 one-token rows (nine packed blocks), 16 rows, 130k tokens of context,
    # 527 tokens x 8 choices of which half fall on the held experts: a mean of 8.2 rows over the 256 routed
    record = {"kind": "mixed", "steps": 1, "batch": 16, "kda_blocks": 54.0, "state_rows": 15.0, "attn_wide_tokens": 512,
              "context_tokens": 130_000, "expert_tokens_mean": 527 * 4 / 256}
    want = (527 * 816_480_256 + 7 * 527 * 4 * 2 * shared + 54 * 268_435_456 + 2 * 2 * 32 * (2 * 512 + 64) * 130_000
            + 16 * 2 * 2304 * 163840)
    assert kda_bytes.mixed_step_flops(CONFIG, record) == want and 0.6e12 < want < 0.8e12
    assert kda_bytes.mixed_step_flops(CONFIG, {k: v for k, v in record.items() if k != "kda_blocks"}) is None
    assert kda_bytes.chunk_work(CONFIG, [record, record]) == (108 * 268_435_456, 108 * 5_242_880)
    assert kda_bytes.chunk_work(CONFIG, [{"kind": "mixed"}]) is None and kda_bytes.chunk_work(CONFIG, []) is None
    decode = {"kind": "decode", "steps": 8, "state_rows": 256.0, "kda_blocks": 0.0}      # 32 rows x 8 steps, summed
    assert kda_bytes.update_bytes(CONFIG, [record, decode]) == (15 + 256) * 6 * 2 * state
    assert kda_bytes.update_bytes(CONFIG, [{"kind": "decode", "steps": 8}]) is None


def test_the_four_readers_on_hand_built_sources():
    recs = [{"kind": "mixed", "steps": 1, "start_ts": 10.0 + i, "batch": 16, "kda_blocks": 54.0, "state_rows": 14.0 + i,
             "attn_wide_tokens": 512, "context_tokens": 130_000 + 1000 * i, "expert_tokens_mean": 8.2} for i in range(5)]
    recs.append({"kind": "mixed", "steps": 1, "start_ts": 15.0})                       # a record without the counts
    capture = {"capture_epoch_ns": [10.5e9, 13.5e9]}
    chip = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"busy_s": 2.9, "mixed_step_s": 0.03, "kda_ops": {"seconds": 0.5, "events": 9000},
             "kda_chunk_kernel": {"seconds": 0.3, "events": 54}, "kda_state_update_ops": {"seconds": 0.004, "events": 54}}
    src = {"config": CONFIG, "timeline": recs, "chip": chip, "status": {"jax_trace": capture}, "trace": trace}
    inside = recs[1:4]
    assert reader("kda_busy_share").compute(src) == 100.0 * 0.5 / 2.9
    chunk = reader("kda_chunk_roofline_share")
    flops, moved = 3 * 54 * 268_435_456, 3 * 54 * 5_242_880
    assert chunk.compute(src) == 100.0 * max(flops / 197e12, moved / 819e9) / 0.3 and 0.0 < chunk.compute(src) < 100.0
    state = reader("kda_state_hbm_roofline_share")
    rows = sum(r["state_rows"] for r in inside)
    assert state.compute(src) == 100.0 * (rows * 6 * 2 * 2_097_152 / 819e9) / 0.004 and 10.0 < state.compute(src) < 100.0
    mfu = reader("kimi_mixed_step_mfu")
    counts = sorted(kda_bytes.mixed_step_flops(CONFIG, r) for r in inside)
    assert mfu.compute(src) == 100.0 * (counts[1] / 197e12) / 0.03 and 5.0 < mfu.compute(src) < 100.0
    # nothing to read: no trace, another configuration, no capture window, a record without a counter, and
    # a trace that holds no such operation (the parent commit's program)
    other = json.loads((BENCH / "configs" / "granite-4.0-h-small-int8.json").read_text())
    empty = {"busy_s": 2.9, "mixed_step_s": 0.03, "kda_ops": {"seconds": 0.0, "events": 0},
             "kda_chunk_kernel": {"seconds": 0.0, "events": 0}, "kda_state_update_ops": {"seconds": 0.0, "events": 0}}
    for name in NEW_READERS:
        r = reader(name)
        assert r.compute({**src, "trace": None}) is None and r.compute({**src, "config": other}) is None, name
        if name != "kimi_mixed_step_mfu":
            assert r.compute({**src, "trace": empty}) is None, name
    for r in (chunk, state, mfu):
        assert r.compute({**src, "status": {}}) is None
        assert r.compute({**src, "timeline": [recs[1], {**recs[5], "start_ts": 12.0}]}) is None
    assert mfu.compute({**src, "trace": {"busy_s": 2.9}}) is None
    # Granite's four readers and Xing's three say nothing of this file, and these four nothing of theirs
    for name in ("ssd_busy_share", "granite_mixed_step_mfu", "mla_attn_busy_share", "mla_step_hbm_roofline_share"):
        assert reader(name).compute(src) is None, name
    # what the patterns find in an instruction's text (events of the compiled step programs, PR 54)
    from mla_bytes import kernel_pattern

    one = re.compile(kernel_pattern(kda_bytes.CHUNK_KERNEL)).search
    assert one("%kda_chunk_kernel.10 = (f32[576,4096]{1,0:T(8,128)}, f32[6,32,4096,128]{3,2,1,0}) custom-call(%a)")
    assert not one("%fusion.3 = f32[544,32,128]{2,1,0} fusion(%kda_chunk_kernel.10)")
    found = re.compile(kda_bytes.update_pattern(CONFIG)).search
    assert found("%kda_state_update_kernel.10 = (f32[32,1,4096]{2,1,0:T(1,128)}, f32[6,32,4096,128]{3,2,1,0}) custom-call(%o)")
    assert not found("%kda_chunk_kernel.10 = (f32[576,4096]{1,0}, f32[6,32,4096,128]{3,2,1,0}) custom-call(%a)")
    assert not found("%while.59 = (s32[], bf16[1,544,2304], f32[6,32,3,12288], f32[6,32,32,128,128]) while(%t)")
    found = re.compile(kda_bytes.conv_pattern(CONFIG)).search
    assert found("%bitcast_dynamic-update-slice_fusion.4 = f32[6,32,3,12288]{3,1,2,0:T(8,128)} fusion(%get-tuple-element.1)")
    assert found("%fusion.640 = f32[544,12288]{0,1:T(8,128)} fusion(%p)") and found("%fusion.7 = f32[32,4,12288]{2,1,0} fusion(%p)")
    assert not found("%fusion.641 = bf16[544,2304]{1,0} fusion(%p)")
    found = re.compile(kda_bytes.scan_pattern(CONFIG)).search
    assert found("%kda_chunk_kernel.11 = (f32[576,4096]{1,0}, f32[6,32,4096,128]{3,2,1,0}) custom-call(%a)")
    assert found("%pad_fusion.8 = f32[576,4096]{1,0:T(8,128)} fusion(%p)") and found("%fusion.640 = f32[544,12288]{0,1} fusion(%p)")
    assert found("%kda_state_update_kernel.11 = (f32[32,1,4096]) custom-call(%o)")
    assert not found("%while.59 = (s32[], bf16[1,544,2304], f32[6,32,3,12288], f32[6,32,32,128,128]) while(%t)")
    assert not found("%moe_gate_up_gmm.3 = bf16[6528,1024]{1,0} custom-call(%x)")


def test_reference_child_on_probes_of_a_tiny_engine_and_the_four_controls(tmp_path):
    """Probes of 85-225 prompt tokens served through mixed steps of 64 tokens
    (eight blocks of 8 a chunk, each probe's states handed over block and chunk
    boundaries beside one-token rows) and decode chunks by the rehearsal's own
    engine; then ``reference.py --rehearse-cpu`` as ``run.py`` starts it, on the
    file as it is: the UNEDITED child finds every leaf the reference declares in
    the program's four stacks, the 4-of-8 share among them. Then the four
    controls through the same child, each NOT ok."""
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    run_cfg = CONFIG["rehearsal"]
    args = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    engine = Engine(EngineConfig(
        model=run_cfg["preset"], quantize=args["--quantize"], dtype=args["--dtype"], attention=args["--attention"],
        page_size=int(args["--page-size"]), max_slots=int(args["--max-slots"]), max_seq_len=int(args["--max-seq-len"]),
        mixed_step=True, mixed_step_tokens=int(run_cfg["sidecar_env"]["SERVING_MIXED_STEP_TOKENS"])))
    assert engine.mixed_ok and engine.carries_state and engine.is_mla and set(engine.cache) == {"kv", "conv", "kda"}
    rng = np.random.default_rng(54)
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
    controls = {"a": {"delta_rule": False}, "b": {"decay_per_channel": False}, "c": {"state_restart_every": 36},
                "d": {"absent_experts": "computed"}}
    for name, switch in controls.items():
        model = {**run_cfg["model"], "assumed_mechanisms": {**run_cfg["model"]["assumed_mechanisms"], **switch}}
        control_file = tmp_path / f"control-{name}.json"
        control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**run_cfg, "model": model}}))
        out, control = child(control_file)
        assert out.returncode == 1 and control["ok"] is False, (name, control)
        assert control["mean_abs_diff"] > 3 * result["mean_abs_diff"], (name, control)
