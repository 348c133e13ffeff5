"""The openPangu-Ultra-MoE configuration's files (PR 56): the catalog row's keys
unchanged but the four in ``reduced``, what was assumed stated, the bytes of
``deployment`` equal to ``eval_shape`` of the program's own initialiser,
``mtp_bytes.py``'s counts against arithmetic by hand, the four new readers on
hand-built sources, the entries found by name and after Kimi's, and the
reference child on probes of a tiny engine whose rounds carry two queries a row,
with the four controls through the same child."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import numpy as np

import mtp_bytes
import reference
from test_reference_child import serve_probe

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "openpangu-ultra-moe-718b.reason"
CONFIG_FILE = BENCH / "configs" / "openpangu-ultra-moe-718b-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
# `config` of the catalog's row (architectures.jsonl, openPangu-Ultra-MoE-718B), every key
SOURCE = {"attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu", "hidden_size": 7680,
          "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
          "moe_intermediate_size": 2048, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
          "num_attention_heads": 128, "num_experts_per_tok": 8, "num_hidden_layers": 61, "num_key_value_heads": 128,
          "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
          "rms_norm_eps": 1e-05, "rope_theta": 25600000, "routed_scaling_factor": 2.5, "sandwich_norm": True,
          "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 7, "first_k_dense_replace": 1, "n_routed_experts": 16, "vocab_size": 19200}
NEW_READERS = ["mtp_accept_share", "mtp_tokens_per_round", "pangu_decode_hbm_roofline_share", "pangu_step_mfu"]
MECHANISMS = {"output_norms": True, "absent_experts": "left_out", "own_key": True, "head_first_row": 0}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_rows_keys_but_the_four_reduced_and_states_what_it_assumed():
    for key, value in SOURCE.items():
        want = REDUCED.get(key, value)
        assert CONFIG[key] == want and type(CONFIG[key]) is type(want), key
    assert CONFIG["reduced"] == list(REDUCED) and set(CONFIG["published"]) >= set(REDUCED)
    assert CONFIG["source"].endswith("FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json")
    assert (CONFIG["routed_experts"], CONFIG["num_local_experts"], CONFIG["vocab_published"]) == (256, 16, 153600)
    for said in ("sandwich_norm", "latent_attention", "rotary", "router", "ffn", "module_input_order", "module_stream",
                 "module_shared", "round", "quantisation", "seeding"):
        assert CONFIG["assumed"][said], said
    for said in ("module_input_order", "module_stream", "module_shared", "sandwich_norm", "router"):
        assert "Alternative" in CONFIG["assumed"][said], said       # each assumed item names what else it could have been
    assert "NO standard deviation is chosen for its effect on acceptance" in CONFIG["assumed"]["seeding"]
    assert CONFIG["assumed_mechanisms"] == MECHANISMS
    assert "ONE of the 16 chips that share each layer" in CONFIG["deployment"]
    assert CONFIG["sidecar_args"] == ["--max-slots", "64", "--max-seq-len", "2048", "--page-size", "128",
                                      "--quantize", "int8", "--attention", "paged", "--dtype", "bfloat16"]
    assert "--spec-draft" not in CONFIG["sidecar_args"] and not any("SPEC" in k for k in CONFIG["sidecar_env"])
    probes = CONFIG["probes"]
    assert probes["before_window_chars"] == [15] and probes["in_window_chars"] == [111, 239, 375, 1475]
    tiny = CONFIG["rehearsal"]
    assert tiny["preset"] == "openpangu-test-tiny" and CONFIG["preset"] == "openpangu-ultra-moe-718b-stage7"
    ref = reference.load_reference(CONFIG["reference"])
    assert CONFIG["reference"] == "mtp_mla_moe"
    for model in (CONFIG, tiny["model"]):
        s = ref.sizes(model)
        assert s["output_norms"] is True and s["absent_experts"] == "left_out" and s["own_key"] is True
        assert s["first_k_dense_replace"] == 1 and s["num_nextn_predict_layers"] == 1 and s["routed_scaling_factor"] == 2.5
    assert ref.sizes(CONFIG)["n_routed_experts"] * 16 == ref.sizes(CONFIG)["routed_experts"]
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == (
        "openpangu-ultra-moe-718b-stage7", "int8", "bfloat16", "dense", 1)
    # the program's preset holds the file's sizes
    sys.path.insert(0, str(ROOT))
    from inference_gateway_tpu.models import mixtral

    for preset, model in ((CONFIG["preset"], CONFIG), (tiny["preset"], tiny["model"])):
        p = mixtral.PRESETS[preset]
        assert (p.hidden_size, p.num_heads, p.vocab_size, p.vocab_published, p.dense_intermediate_size, p.intermediate_size,
                p.num_experts, p.held_experts, p.experts_per_token, p.num_dense_layers, p.shared_experts, p.route_scale,
                p.num_layers, p.mtp_layers, p.sandwich_norm) == tuple(model[k] for k in (
                    "hidden_size", "num_attention_heads", "vocab_size", "vocab_published", "intermediate_size",
                    "moe_intermediate_size", "routed_experts", "n_routed_experts", "num_experts_per_tok",
                    "first_k_dense_replace", "n_shared_experts", "routed_scaling_factor", "num_hidden_layers",
                    "num_nextn_predict_layers", "sandwich_norm"))
        assert (p.kv_lora_rank, p.q_lora_rank, p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim, p.rms_norm_eps,
                p.rope_theta) == tuple(model[k] for k in ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                                                          "v_head_dim", "rms_norm_eps", "rope_theta"))
        assert p.rope_scaling is None and p.layer_rope is None


def test_the_deployments_bytes_are_eval_shape_of_the_programs_own_initialiser():
    sys.path.insert(0, str(ROOT))
    import jax

    from inference_gateway_tpu.models import mixtral
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    big = mixtral.PRESETS[CONFIG["preset"]]
    tree = jax.eval_shape(lambda k: init_quantized_llama_params(k, big), jax.random.PRNGKey(0))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))
    layers = tree["layers"]
    dense = nbytes({n: w for n, w in layers.items() if n.startswith("dense_")})
    expert = nbytes({n: w for n, w in layers.items() if not n.startswith("dense_")})
    one = lambda n: nbytes(layers[n]) // 6
    pool = nbytes(jax.eval_shape(lambda: mixtral.init_paged_cache(big, PagedCacheConfig(128, 0, 64, 2048))))
    said = (one("wdq"), one("wuq"), one("wdkv"), one("wukv"), one("wo"), dense, expert // 6,
            one("wg") + one("wu") + one("wd"), 3 * one("shared_wg"), one("router"), nbytes(tree["mtp"]),
            nbytes(tree["mtp"]["eh_proj"]), nbytes(tree["embed"]), nbytes(tree["lm_head"]), nbytes(tree), pool)
    assert said == (11_802_624, 37_847_040, 4_425_984, 16_908_288, 125_859_840, 621_760_768, 1_003_803_392, 755_728_384,
                    47_210_496, 3_932_160, 1_121_844_992, 117_995_520, 294_912_000, 147_532_800, 8_208_886_272, 1_342_177_280)
    assert all(f"{n:,}" in CONFIG["deployment"] for n in said)
    assert nbytes(tree["mtp"]["layers"]) == expert // 6                       # the module's block is one such layer
    plan = nbytes(tree) + pool
    assert round(plan / 1e9, 2) == 9.55 and "plan 9.55 GB" in CONFIG["deployment"] and plan > 0.25 * 17.18e9
    # the issue's arithmetic from the row's sizes (matrices alone, no scales): within 0.2% of the tree
    attention = 7680 * 1536 + 1536 * 24576 + 7680 * 576 + 512 * 32768 + 16384 * 7680
    assert attention == 196_575_232 and mtp_bytes.attention_params(CONFIG) == attention
    by_hand = (attention + 3 * 7680 * 18432) + 7 * (attention + 17 * 3 * 7680 * 2048 + 7680 * 256 * 2) + 2 * 7680 * 7680 + \
        19200 * 7680 * 3
    assert by_hand == 8_200_257_536 and abs(nbytes(tree) - by_hand) / by_hand < 0.002


def test_the_benchmark_gains_one_configuration_one_cell_and_four_readers_after_kimis():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    index = lambda entries, name: [e["name"] for e in entries].index(name)
    config = bench["configs"][index(bench["configs"], "openpangu-ultra-moe-718b-int8")]
    assert index(bench["configs"], config["name"]) > index(bench["configs"], "kimi-linear-48b-a3b-int8")
    assert config["reduced"] == CONFIG["reduced"] == list(REDUCED)
    assert config["source"] == CONFIG["source"] and config["file"] == "bench/configs/openpangu-ultra-moe-718b-int8.json"
    assert index(bench["workloads"], CELL) > index(bench["workloads"], "kimi-linear-48b-a3b.long-context")
    cell = bench["workloads"][index(bench["workloads"], CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, "openpangu-ultra-moe-718b-int8", "reason-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("64 rows x 2 queries", "16 of 256 experts", "16x a chip's rows", "seeded weights'"):
        assert said in cell["why"], said
    first = index(bench["per_layer"], NEW_READERS[0])
    assert first > index(bench["per_layer"], "kimi_mixed_step_mfu")
    assert [m["name"] for m in bench["per_layer"][first:first + 4]] == NEW_READERS
    for m in bench["per_layer"][first:first + 4]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert m["better"] == "higher" and m["layer"] in {o["layer"] for o in bench["per_layer"][:first]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # neither side reports fetch_return_ms since PR 51; the three mla_* readers answer for Xing alone, Kimi's and
    # Granite's for theirs; the dense and mixture roofline readers count one token a row a step
    for name in ("fetch_return_ms", "mla_attn_busy_share", "mla_attn_roofline_share", "mla_step_hbm_roofline_share",
                 "mhc_busy_share", "kda_busy_share", "kimi_mixed_step_mfu", "granite_mixed_step_mfu",
                 "decode_hbm_roofline_share", "moe_decode_hbm_roofline_share", "scope_state_share", "tpot_p95_ms"):
        assert CELL not in by_name[name]["workloads"], name
    accepted = [w["name"] for w in bench["workloads"][:index(bench["workloads"], CELL)]]
    for name in ("decode_batch_mean", "ttft_p50_ms.batch", "decode_step_ms", "attn_kernel_busy_share",
                 "mixed_step_host_ms.batch", "host_starved_share", "idle_unexplained_share", "idle_unspanned_share",
                 "starved_slot_share", "starved_delivery_share", "starved_unspanned_share", "start_import_s",
                 "start_engine_build_s", "post_warmup_compile_s", "experts_read_mean", "moe_busy_share",
                 "scope_attention_share", "scope_mlp_share", "scope_moe_share", "scope_head_sampling_share",
                 "scope_unscoped_share"):
        listed = by_name[name]["workloads"]
        assert all(listed.index(CELL) > listed.index(w) for w in accepted if w in listed), name
    mix = json.loads((BENCH / "traffic" / "reason-closed.json").read_text())        # the accepted file, unedited
    assert (mix["loop"], mix["schedule_seed"], mix["max_total_tokens"], mix["sampling"]["temperature"]) == ("closed", 32, 1975, 0.7)
    clients = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"]
    assert clients == CONFIG["deployment_sizes"]["max_slots"] == 64                  # one client a slot


def test_counts_by_hand():
    attention, expert = 196_575_232, 3 * 7680 * 2048
    assert mtp_bytes.expert_params(CONFIG) == expert == 47_185_920
    s = mtp_bytes.sizes(CONFIG)
    assert (s["L"], s["lead"], s["modules"], s["held"], s["routed"], s["V"], s["page"], s["chunk"]) == (7, 1, 1, 16, 256, 19200, 128, 8)
    # a chunk of 8 rounds of 64 rows at contexts of ~1,000 tokens: 8 pages a row in 8 planes, 14.6 of 16 experts read
    record = {"kind": "decode", "steps": 8, "experts_read": 14.6, "latent_pages_read": 8 * 64 * 8 * 8.0,
              "mtp_drafted": 512.0, "mtp_accepted": 32.0, "mtp_tokens": 544.0}
    weights = 8 * attention + 3 * 7680 * 18432 + 7 * (14.6 + 1) * expert + 2 * 7680 * 7680 + 7680 * 19200
    want = weights + 7 * 7680 * 256 * 2 + 64 * 8 * 8 * 128 * 576 * 2
    assert mtp_bytes.round_bytes(CONFIG, record) == want and 7.5e9 < want < 8.5e9
    module_tokens = 64 * (1 + 32 / 512)
    ffn = (0.5 + 1) * expert
    flops = 2.0 * (2 * 64 * (7 * attention + 3 * 7680 * 18432 + 6 * ffn) + module_tokens * (attention + ffn + 2 * 7680 * 7680)
                   + (128 + module_tokens) * 7680 * 19200 + 2 * 128 * (2 * 512 + 64) * 64 * 8 * 8 * 128)
    assert mtp_bytes.round_flops(CONFIG, record) == flops and 0.7e12 < flops < 1.2e12
    for lacking in ("experts_read", "latent_pages_read", "mtp_drafted"):
        short = {k: v for k, v in record.items() if k != lacking}
        assert mtp_bytes.round_bytes(CONFIG, short) is None and mtp_bytes.round_flops(CONFIG, short) is None
    mixed = {"kind": "mixed", "steps": 1, "mtp_drafted": 60.0, "mtp_accepted": 3.0, "mtp_tokens": 63.0}
    assert mtp_bytes.drafting([record, mixed, {"kind": "mixed", "mtp_drafted": 0.0}]) == {
        "drafted": 572.0, "accepted": 35.0, "tokens": 607.0}
    assert mtp_bytes.drafting([{"kind": "decode", "steps": 8}]) is None and mtp_bytes.drafting(None) is None


def test_the_four_readers_on_hand_built_sources():
    recs = [{"kind": "decode", "steps": 8, "start_ts": 10.0 + i, "experts_read": 14.0 + 0.2 * i,
             "latent_pages_read": 8 * 64 * 8 * (6.0 + i), "mtp_drafted": 512.0, "mtp_accepted": 30.0 + i,
             "mtp_tokens": 542.0 + i} for i in range(5)]
    recs.append({"kind": "mixed", "steps": 1, "start_ts": 12.2, "mtp_drafted": 60.0, "mtp_accepted": 3.0, "mtp_tokens": 63.0})
    recs.append({"kind": "decode", "steps": 8, "start_ts": 15.0})                       # a record without the counts
    capture = {"capture_epoch_ns": [10.5e9, 13.5e9]}
    chip = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"busy_s": 2.9, "decode_chunk_s": 0.112}
    src = {"config": CONFIG, "timeline": recs, "chip": chip, "status": {"jax_trace": capture}, "trace": trace}
    inside = recs[1:4]
    drafted, accepted, tokens = 5 * 512 + 60, sum(30 + i for i in range(5)) + 3, sum(542 + i for i in range(5)) + 63
    assert reader("mtp_accept_share").compute(src) == 100.0 * accepted / drafted
    assert reader("mtp_tokens_per_round").compute(src) == tokens / drafted and 1.0 < tokens / drafted < 2.0
    hbm = reader("pangu_decode_hbm_roofline_share")
    counts = sorted(mtp_bytes.round_bytes(CONFIG, r) for r in inside)
    assert hbm.compute(src) == 100.0 * (counts[1] / 819e9) / (0.112 / 8) and 50.0 < hbm.compute(src) < 100.0
    mfu = reader("pangu_step_mfu")
    counts = sorted(mtp_bytes.round_flops(CONFIG, r) for r in inside)
    assert mfu.compute(src) == 100.0 * (counts[1] / 197e12) / (0.112 / 8) and 10.0 < mfu.compute(src) < 100.0
    # nothing to read: no trace, another configuration, no capture window, a record without a counter (the parent)
    other = json.loads((BENCH / "configs" / "xing4.0-29b-a4b-int8.json").read_text())
    for r in (hbm, mfu):
        assert r.compute({**src, "trace": None}) is None and r.compute({**src, "config": other}) is None
        assert r.compute({**src, "status": {}}) is None and r.compute({**src, "trace": {"busy_s": 2.9}}) is None
        assert r.compute({**src, "timeline": [recs[1], {**recs[6], "start_ts": 12.0}]}) is None
    bare = [{"kind": "decode", "steps": 8, "start_ts": 11.0, "experts_read": 14.0}, {"kind": "mixed", "steps": 1, "start_ts": 12.0}]
    for name in ("mtp_accept_share", "mtp_tokens_per_round"):
        assert reader(name).compute({**src, "timeline": bare}) is None and reader(name).compute({**src, "timeline": []}) is None
    # Xing's and Kimi's readers say nothing of this file
    for name in ("mla_attn_busy_share", "mla_step_hbm_roofline_share", "kimi_mixed_step_mfu", "kda_busy_share"):
        assert reader(name).compute(src) is None, name


def test_reference_child_on_probes_of_a_tiny_engine_and_the_four_controls(tmp_path):
    """Probes of 85-225 prompt tokens served through mixed steps of 64 tokens and
    decode chunks of two-query rounds by the rehearsal's own engine, the module
    drafting; then ``reference.py --rehearse-cpu`` as ``run.py`` starts it, on the
    file as it is: the UNEDITED child builds the tree (the module's leaves in it),
    finds every leaf the reference declares in the program's two stacks, and
    compares over the held slice of the vocabulary. Then the four controls
    through the same child: the norms, the experts and the head's rows read NOT
    ok; a query that misses its own key reads ok at seeded weights (one key of
    the dozens a head rests on: PERF.md section 6), and well above the sound run."""
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    run_cfg = CONFIG["rehearsal"]
    args = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    engine = Engine(EngineConfig(
        model=run_cfg["preset"], quantize=args["--quantize"], dtype=args["--dtype"], attention=args["--attention"],
        page_size=int(args["--page-size"]), max_slots=int(args["--max-slots"]), max_seq_len=int(args["--max-seq-len"]),
        mixed_step=True, mixed_step_tokens=int(run_cfg["sidecar_env"]["SERVING_MIXED_STEP_TOKENS"])))
    assert engine.mixed_ok and engine.is_mla and engine.mtp and set(engine.cache) == {"kv"}
    rng = np.random.default_rng(56)
    scheduler = Scheduler(engine)
    scheduler.start()
    try:
        probes = [serve_probe(scheduler, CONFIG, "".join(chr(c) for c in rng.integers(32, 127, size=chars)), 8)
                  for chars in (60, 100, 200)]
    finally:
        scheduler.stop()
    assert scheduler.spec_slot_rounds > 0
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
    controls = {"1": {"output_norms": False}, "2": {"absent_experts": "computed"}, "3": {"own_key": False},
                "4": {"head_first_row": 128}}
    for name, switch in controls.items():
        model = {**run_cfg["model"], "assumed_mechanisms": {**run_cfg["model"]["assumed_mechanisms"], **switch}}
        control_file = tmp_path / f"control-{name}.json"
        control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**run_cfg, "model": model}}))
        out, control = child(control_file)
        if name == "3":
            assert control["mean_abs_diff"] > 1.3 * result["mean_abs_diff"], (name, control)
            continue
        assert out.returncode == 1 and control["ok"] is False, (name, control)
        assert control["mean_abs_diff"] > 3 * result["mean_abs_diff"], (name, control)
