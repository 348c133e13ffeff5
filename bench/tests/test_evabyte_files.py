"""The EvaByte configuration's files (PR 61): the catalog row's keys unchanged but
``num_pred_heads``, what was assumed stated with its alternative, the bytes of
``deployment`` equal to ``eval_shape`` of the program's own initialiser and pool,
``eva_bytes.py``'s counts against arithmetic by hand, the four new readers on
hand-built sources, the entries found BY NAME, and the reference child on probes of
a tiny engine that fold windows in prefill and in decode, with four of the five
controls through the same child."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import numpy as np

import eva_bytes
import reference
from test_reference_child import serve_probe

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "evabyte-6.5b.long-context"
CONFIG_FILE = BENCH / "configs" / "evabyte-6.5b-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
# `config` of the catalog's row (architectures.jsonl, EvaByte), every key
SOURCE = {"attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
          "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None, "init_fn": "v2",
          "init_std": 0.01275, "intermediate_size": 11008, "lazy_init": True, "max_position_embeddings": 32768,
          "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte", "norm_add_unit_offset": True,
          "num_attention_heads": 32, "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
          "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
          "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}
REDUCED = {"num_pred_heads": 1}
NEW_READERS = ["eva_kv_read_share", "eva_decode_hbm_roofline_share", "evabyte_step_mfu", "eva_fold_busy_share"]
MECHANISMS = {"pooling": "softmax", "summaries": "earlier_windows", "precision": "float32"}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_rows_keys_but_the_one_reduced_and_states_what_it_assumed():
    for key, value in SOURCE.items():
        want = REDUCED.get(key, value)
        assert CONFIG[key] == want and type(CONFIG[key]) is type(want), key
    assert CONFIG["reduced"] == list(REDUCED) and set(CONFIG["published"]) == set(REDUCED)
    assert CONFIG["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    for said in ("a_pooling_weights", "b_rotation_before_pooling", "c_one_scale", "d_head_zero"):
        assert "Alternative" in CONFIG["assumed"][said], said       # each names what else it could have been
    assert "NOT scaled down" in CONFIG["assumed"]["e_seeding"]
    assert CONFIG["assumed_mechanisms"] == MECHANISMS == CONFIG["rehearsal"]["model"]["assumed_mechanisms"]
    assert "WHOLE model" in CONFIG["deployment"] and "tensor_parallel 1" in CONFIG["deployment"]
    assert CONFIG["sidecar_args"] == ["--max-slots", "5", "--max-seq-len", "12800", "--page-size", "128",
                                      "--quantize", "int8", "--attention", "paged", "--dtype", "bfloat16"]
    assert (CONFIG["tokenizer"]["bos_id"], CONFIG["tokenizer"]["eos_id"]) == (256, 257) and CONFIG["vocab_size"] > 257
    probes = CONFIG["probes"]
    assert probes["before_window_chars"] == [111] and probes["in_window_chars"] == [111, 1475, 2018, 4975, 9000]
    template = len(reference.prompt_ids([{"role": "user", "content": ""}], CONFIG["tokenizer"], CONFIG["chat_template"]))
    assert template + 2018 == 2043 and 2043 + probes["max_tokens"] > 2048      # its 8 tokens cross a window's end
    tiny = CONFIG["rehearsal"]
    assert (CONFIG["preset"], tiny["preset"], CONFIG["reference"]) == ("evabyte-6.5b", "evabyte-test-tiny", "eva_dense")
    ref = reference.load_reference(CONFIG["reference"])
    for model in (CONFIG, tiny["model"]):
        s = ref.sizes(model)
        assert {k: s[k] for k in MECHANISMS} == MECHANISMS and s["norm_add_unit_offset"] is True
        assert s["window_size"] // s["chunk_size"] == (128 if model is CONFIG else 8)
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == ("evabyte-6.5b", "int8", "bfloat16", "dense", 1)
    # the program's preset holds the file's sizes
    sys.path.insert(0, str(ROOT))
    from inference_gateway_tpu.models import llama

    for preset, model in ((CONFIG["preset"], CONFIG), (tiny["preset"], tiny["model"])):
        p = llama.PRESETS[preset]
        assert (p.hidden_size, p.intermediate_size, p.num_layers, p.num_heads, p.num_kv_heads, p.vocab_size, p.rope_theta,
                p.rms_norm_eps, p.eva_window, p.eva_chunk, p.norm_offset, p.num_pred_heads, p.tie_word_embeddings) == tuple(
                    model[k] for k in ("hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
                                       "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps", "window_size",
                                       "chunk_size", "norm_add_unit_offset", "num_pred_heads", "tie_word_embeddings"))
        assert p.rope_scaling is None and p.sliding_window is None
    assert llama.PRESETS[CONFIG["preset"]].max_position_embeddings == CONFIG["max_position_embeddings"]


def test_the_deployments_bytes_are_eval_shape_of_the_programs_own_initialiser_and_pool():
    sys.path.insert(0, str(ROOT))
    import jax

    from inference_gateway_tpu.models import llama
    from inference_gateway_tpu.models.family import family_of
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    big = llama.PRESETS[CONFIG["preset"]]
    tree = jax.eval_shape(lambda k: init_quantized_llama_params(k, big), jax.random.PRNGKey(0))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))
    q = lambda n: tree["layers"][n].q.size // 32                                  # a layer's int8 matrix
    layer = sum(q(n) for n in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"))
    assert (4 * q("wq"), q("wg") + q("wu") + q("wd"), layer, 32 * layer) == (67_108_864, 135_266_304, 202_375_168, 6_476_005_376)
    sizes = CONFIG["deployment_sizes"]
    page_cfg = PagedCacheConfig(sizes["page_size"], 0, sizes["max_slots"], sizes["max_seq_len"], rows=family_of(big).cached_rows)
    pool = nbytes(jax.eval_shape(lambda: llama.init_paged_cache(big, page_cfg)))
    row = 2 * 32 * 128 * 2
    assert (row, 32 * row, 128 * 32 * row) == (16_384, 524_288, 67_108_864) == (
        sizes["kv_row_bytes_per_layer"], 32 * sizes["kv_row_bytes_per_layer"], pool // 105)
    assert (page_cfg.max_pages_per_slot, page_cfg.resolve_num_pages()) == (21, 105) == (sizes["pages_per_slot"], sizes["pool_pages"])
    assert page_cfg.peak_rows(12288) == 5 * 128 + 2048 == 2688 and -(-12800 // 128) == 100   # 21 pages where full attention holds 100
    said = (67_108_864, 135_266_304, 202_375_168, 6_476_005_376, 16_384, 524_288)
    assert all(f"{n:,}" in CONFIG["deployment"] for n in said) and sizes["layer_matrix_bytes"] == layer
    assert round(nbytes(tree) / 1e9, 2) == 6.49 and "6.49 GB" in CONFIG["deployment"]
    assert round(pool / 1e9, 2) == 7.05 and "7.05 GB" in CONFIG["deployment"]
    plan = nbytes(tree) + pool
    assert round(plan / 1e9, 2) == 13.53 and "13.53 GB planned" in CONFIG["deployment"] and plan > 0.25 * 17.18e9
    assert nbytes(tree["lm_head"]) < 1.4e6 and nbytes(tree["embed"]) == 320 * 4096 * 2   # one head of the eight is held


def test_the_benchmark_gains_one_configuration_one_cell_and_four_readers_found_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    index = lambda entries, name: [e["name"] for e in entries].index(name)
    config = bench["configs"][index(bench["configs"], "evabyte-6.5b-int8")]
    assert index(bench["configs"], config["name"]) > index(bench["configs"], "openpangu-ultra-moe-718b-int8")
    assert config["reduced"] == CONFIG["reduced"] == list(REDUCED)
    assert config["source"] == CONFIG["source"] and config["file"] == "bench/configs/evabyte-6.5b-int8.json"
    assert index(bench["workloads"], CELL) > index(bench["workloads"], "openpangu-ultra-moe-718b.reason")
    cell = bench["workloads"][index(bench["workloads"], CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, "evabyte-6.5b-int8", "longctx-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for said in ("5 closed clients on 5 slots", "128-640 summaries", "folds 2-5 windows", "bypasses prefix cache"):
        assert said in cell["why"], said
    first = index(bench["per_layer"], NEW_READERS[0])
    assert first > index(bench["per_layer"], "pangu_step_mfu")
    assert [m["name"] for m in bench["per_layer"][first:first + 4]] == NEW_READERS
    better = dict(zip(NEW_READERS, ("lower", "higher", "higher", "lower")))
    for m in bench["per_layer"][first:first + 4]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert m["better"] == better[m["name"]] and m["layer"] in {o["layer"] for o in bench["per_layer"][:first]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # ops_bytes.py counts a cached row a token (it would read over 100% here); fetch_return_ms is dead since PR 51;
    # 30 requests a window carry no tail; the other families' readers answer for theirs
    for name in ("decode_hbm_roofline_share", "fetch_return_ms", "tpot_p95_ms", "ttft_p95_ms", "moe_busy_share",
                 "scope_moe_share", "scope_state_share", "dsa_kv_read_share", "pangu_step_mfu", "granite_mixed_step_mfu"):
        assert CELL not in by_name[name]["workloads"], name
    accepted = [w["name"] for w in bench["workloads"][:index(bench["workloads"], CELL)]]
    for name in ("decode_batch_mean", "ttft_p50_ms.batch", "decode_step_ms", "attn_kernel_busy_share",
                 "mixed_step_host_ms.batch", "host_starved_share", "idle_unexplained_share", "idle_unspanned_share",
                 "starved_slot_share", "starved_delivery_share", "starved_unspanned_share", "start_import_s",
                 "start_engine_build_s", "post_warmup_compile_s", "scope_attention_share", "scope_mlp_share",
                 "scope_head_sampling_share", "scope_unscoped_share"):
        listed = by_name[name]["workloads"]
        assert all(listed.index(CELL) > listed.index(w) for w in accepted if w in listed), name
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL not in end_to_end["ttft_p50_ms"]["workloads"] and "workloads" not in end_to_end["tpot_p50_ms"]
    mix = json.loads((BENCH / "traffic" / "longctx-closed.json").read_text())       # the accepted file, unedited
    assert (mix["loop"], mix["schedule_seed"], mix["ramp_s"], mix["max_total_tokens"]) == ("closed", 36, 8, 12700)
    clients = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"]
    assert clients == CONFIG["deployment_sizes"]["max_slots"] == 5                   # one client a slot


def test_counts_by_hand():
    s = eva_bytes.sizes(CONFIG)
    assert (s["L"], s["H"], s["I"], s["heads"], s["V"], s["W"], s["C"], s["steps"]) == (32, 4096, 11008, 32, 320, 2048, 16, 8)
    assert eva_bytes.matrix_params(CONFIG) == 6_476_005_376
    # a chunk of 8 steps of 5 rows at contexts of ~8,350 tokens: window 4, 512 summaries + 158 exact rows a row
    record = {"kind": "decode", "steps": 8, "batch": 5, "eva_rows": 8 * 5 * 670.0, "eva_context": 8 * 5 * 8350.0,
              "eva_rolls": 0.0, "eva_pairs": 8 * 5 * 670.0, "eva_queries": 40.0}
    want = 6_476_005_376 + 4096 * 320 + 5 * 670 * 16_384 * 32
    assert eva_bytes.decode_step_bytes(CONFIG, record) == want and 8.2e9 < want < 8.3e9
    assert eva_bytes.read_share([record]) == 100.0 * 670 / 8350
    # a mixed step: a 512-token chunk that ends at 8,192 tokens (window 3: 384 summaries + 2,048 rows) beside 4 decode rows
    rows = 384 + 2048
    mixed = {"kind": "mixed", "steps": 1, "batch": 5, "eva_rows": rows + 4 * 670.0, "eva_context": 8192 + 4 * 8350.0,
             "eva_rolls": 0.0, "eva_pairs": 512 * rows - 512 * 511 // 2 + 4 * 670.0, "eva_queries": 516.0}
    flops = 2.0 * (516 * 6_476_005_376 + 5 * 4096 * 320 + 2 * 4096 * mixed["eva_pairs"] * 32)
    assert eva_bytes.mixed_step_flops(CONFIG, mixed) == flops and 7.0e12 < flops < 7.5e12
    for lacking in eva_bytes.COUNTERS:
        short = {k: v for k, v in record.items() if k != lacking}
        assert eva_bytes.decode_step_bytes(CONFIG, short) is None
        assert eva_bytes.mixed_step_flops(CONFIG, {k: v for k, v in mixed.items() if k != lacking}) is None
    assert eva_bytes.read_share([{"kind": "decode", "steps": 8}]) is None and eva_bytes.read_share(None) is None
    # the fold's shapes, and no other operation's: a window's rows, a window by chunks, the summaries by head
    import re

    fold = re.compile(eva_bytes.fold_pattern(CONFIG))
    for text in ("%fusion.9 = f32[128,32,128]{2,1,0} fusion(bf16[2048,4096]{1,0} %concatenate.3)",
                 "%reduce.2 = f32[128,16,32]{2,1,0} reduce(f32[128,16,32,128]{3,2,1,0} %multiply.4)",
                 "%bitcast.7 = bf16[2048,32,128]{2,1,0} bitcast(%concatenate.3)"):
        assert fold.search(text), text
    for text in ("%fusion.1 = bf16[517,4096]{1,0} fusion(s8[4096,4096]{1,0} %p)", "%while.3 = (bf16[2048,4096]) while(%tuple)",
                 "%paged_attention_tpu.1 = bf16[5,32,128]{2,1,0} custom-call(bf16[13762560,4096] %pool)"):
        assert not fold.search(text), text


def test_the_four_readers_on_hand_built_sources():
    recs = [{"kind": "decode", "steps": 8, "batch": 5, "start_ts": 10.0 + i, "eva_rows": 8 * 5 * (600.0 + 40 * i),
             "eva_context": 8 * 5 * 8000.0, "eva_rolls": 0.0, "eva_pairs": 8 * 5 * (600.0 + 40 * i), "eva_queries": 40.0}
            for i in range(5)]
    recs += [{"kind": "mixed", "steps": 1, "batch": 5, "start_ts": 10.6 + i, "eva_rows": 2000.0 + 2680, "eva_context": 6000.0 + 32000,
              "eva_rolls": float(i == 1), "eva_pairs": 512 * 2000.0 - 512 * 511 // 2 + 2680, "eva_queries": 516.0} for i in range(3)]
    recs.append({"kind": "decode", "steps": 8, "start_ts": 15.0})                       # a record without the counts
    capture = {"capture_epoch_ns": [10.5e9, 13.5e9]}
    chip = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    trace = {"busy_s": 2.9, "decode_chunk_s": 0.104, "mixed_step_s": 0.047, "eva_fold_ops": {"dsa_s": 0.0116, "dsa_events": 448}}
    src = {"config": CONFIG, "timeline": recs, "chip": chip, "status": {"jax_trace": capture}, "trace": trace}
    counted = [r for r in recs if r.get("eva_context")]
    share = reader("eva_kv_read_share").compute(src)
    assert share == 100.0 * sum(r["eva_rows"] for r in counted) / sum(r["eva_context"] for r in counted) and 5.0 < share < 15.0
    hbm = reader("eva_decode_hbm_roofline_share")
    counts = sorted(eva_bytes.decode_step_bytes(CONFIG, r) for r in recs[1:4])
    assert hbm.compute(src) == 100.0 * (counts[1] / 819e9) / (0.104 / 8) and 50.0 < hbm.compute(src) < 100.0
    mfu = reader("evabyte_step_mfu")
    flops = eva_bytes.mixed_step_flops(CONFIG, recs[5])
    assert mfu.compute(src) == 100.0 * (flops / 197e12) / 0.047 and 50.0 < mfu.compute(src) < 100.0
    fold = reader("eva_fold_busy_share")
    assert fold.compute(src) == 100.0 * 0.0116 / 2.9
    assert fold.compute({**src, "trace": {**trace, "eva_fold_ops": {"dsa_s": 0.0, "dsa_events": 0}}}) == 0.0   # no roll in the capture
    # nothing to read: no trace, another configuration, no capture window, a record without a counter (the parent)
    other = json.loads((BENCH / "configs" / "mistral-7b-int8.json").read_text())
    for r in (hbm, mfu, fold):
        assert r.compute({**src, "trace": None}) is None and r.compute({**src, "config": other}) is None
    for r in (hbm, mfu):
        assert r.compute({**src, "status": {}}) is None and r.compute({**src, "trace": {"busy_s": 2.9}}) is None
        assert r.compute({**src, "timeline": [recs[1], recs[5], {**recs[8], "start_ts": 12.0},
                                              {"kind": "mixed", "start_ts": 12.1, "batch": 5}]}) is None
    assert fold.compute({**src, "trace": {"busy_s": 2.9}}) is None                  # no file to read, none brought
    assert reader("eva_kv_read_share").compute({**src, "timeline": [recs[8]]}) is None
    # the dense roofline reader is not asked about this file's cell; the other families' say nothing of it
    for name in ("pangu_step_mfu", "kimi_mixed_step_mfu", "dsa_kv_read_share", "mla_attn_busy_share"):
        assert reader(name).compute(src) is None, name


def test_reference_child_on_probes_of_a_tiny_engine_and_four_controls(tmp_path):
    """Probes of 52-225 prompt tokens (2-7 windows of 32: chunks cut at each window's
    end, folds in prefill; the 52-token probe's 8 tokens cross position 64: a fold in
    DECODE) served through mixed steps of 64 tokens and decode chunks by the
    rehearsal's own engine; then ``reference.py --rehearse-cpu`` as ``run.py`` starts
    it, on the file as it is: the UNEDITED child builds the tree over a dense cache
    (the two EVA vectors in it, a head of two heads' columns), finds every leaf the
    reference declares, and compares over the first head's 320 columns. Then four
    controls through the same child. Two read NOT ok, dozens of times the sound
    run's mean: no summaries, and stale exact rows where a fold was missed. A
    window's summaries seen one window early (the query's own complete chunks as
    summaries BESIDE their exact keys: a few low-weight duplicates) and a MEAN for
    the pooling (over seeded weights a summary is a near-random vector, and what
    the summaries do to a query is mostly the softmax mass they take, which the
    pooling's form moves by a quarter) move the logprobs a few times the sound
    run's and stay under the harness's one pair of limits, here as on the chip:
    tests/test_evabyte.py holds those two, at 2e-4 nats in float32. Then the
    reference a step of precision below the served path: rounded to bf16 where
    the served path holds bf16 it reads what the sound run reads (that IS the
    served precision); with the int8 matrices rounded again to e4m3 it moves
    away, at this toy's two layers of 64 not yet past the limits (on the chip:
    PERF.md section 6, PR 61)."""
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    run_cfg = CONFIG["rehearsal"]
    args = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    engine = Engine(EngineConfig(
        model=run_cfg["preset"], quantize=args["--quantize"], dtype=args["--dtype"], attention=args["--attention"],
        page_size=int(args["--page-size"]), max_slots=int(args["--max-slots"]), max_seq_len=int(args["--max-seq-len"]),
        mixed_step_tokens=int(run_cfg["sidecar_env"]["SERVING_MIXED_STEP_TOKENS"])))
    assert engine.mixed_ok and set(engine.cache) == {"k", "v"} and engine.page_cfg.max_pages_per_slot == 19
    rng = np.random.default_rng(61)
    scheduler = Scheduler(engine)
    scheduler.start()
    try:
        probes = [serve_probe(scheduler, CONFIG, "".join(chr(c) for c in rng.integers(32, 127, size=chars)), 8)
                  for chars in (27, 100, 200)]
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
    controls = {"a": {"summaries": "none"}, "b": {"summaries": "one_window_early"}, "c": {"pooling": "mean"},
                "d": {"summaries": "stale_rows"}, "e": {"precision": "bfloat16"}, "f": {"precision": "e4m3"}}
    for name, switch in controls.items():
        model = {**run_cfg["model"], "assumed_mechanisms": {**MECHANISMS, **switch}}
        control_file = tmp_path / f"control-{name}.json"
        control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**run_cfg, "model": model}}))
        out, control = child(control_file)
        if name in ("a", "d"):
            assert out.returncode == 1 and control["ok"] is False, (name, control)
        elif name == "e":
            assert control["ok"] and control["mean_abs_diff"] < 2 * result["mean_abs_diff"], (name, control)
        else:
            assert control["mean_abs_diff"] > 2 * result["mean_abs_diff"], (name, control)
