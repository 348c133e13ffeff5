"""The Keye-VL-2.0 configuration's files (PR 36): the catalog row's keys
unchanged but the depth, the cut and what was assumed stated, byte counts
against arithmetic by hand, the two ``dsa_*`` readers on hand-built sources,
and the reference child on probes of a tiny engine whose topk is shorter than
its probes."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import numpy as np

import dsa_bytes
import reference
from test_reference_child import serve_probe, serving_engine

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "keye-vl2-30b-a3b.long-context"
CONFIG_FILE = BENCH / "configs" / "keye-vl2-30b-a3b-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
# `config` of the catalog's row (architectures.jsonl, Keye-VL-2.0-30B-A3B), every key
SOURCE = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
          "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
          "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
          "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
          "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
          "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
          "rope_theta": 10000000,
          "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
          "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_rows_keys_and_states_its_cut_and_what_it_assumed():
    for key, value in SOURCE.items():
        assert CONFIG[key] == value and type(CONFIG[key]) is type(value), key
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] == 8 and CONFIG["published"]["num_hidden_layers"] == 48
    for said in ("indexer", "q_chunk_size_kv_chunk_size", "qk_norm", "mrope", "vision_tower", "router",
                 "intermediate_size", "weights", "tokenizer", "context"):
        assert CONFIG["assumed"][said]
    assert CONFIG["sidecar_args"] == ["--quantize", "int8", "--attention", "paged", "--page-size", "128",
                                      "--max-slots", "12", "--max-seq-len", "12800", "--dtype", "bfloat16"]
    assert CONFIG["reference"] == "moe_dsa_gqa" and CONFIG["deployment_sizes"]["tensor_parallel"] == 1
    probes = CONFIG["probes"]
    assert probes["before_window_chars"] == [3000] and probes["in_window_chars"] == [1500, 2500, 5000, 9000]
    topk = CONFIG["sa_config"]["topk"]
    assert sum(c + 25 <= topk for c in probes["in_window_chars"]) == 1 and probes["max_tokens"] == 8
    tiny = CONFIG["rehearsal"]
    assert tiny["preset"] == "keye-test-tiny"
    assert all(c > tiny["model"]["sa_config"]["topk"] for c in tiny["probes"]["before_window_chars"]
               + tiny["probes"]["in_window_chars"])
    ref = reference.load_reference(CONFIG["reference"])
    for model in (CONFIG, tiny["model"]):
        sizes = ref.sizes(model)
        assert sum(sizes["mrope_section"]) * 2 == sizes["head_dim"] and sizes["topk"] == model["sa_config"]["topk"]
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == (
        "keye-vl2-30b-a3b-stage8", "int8", "bfloat16", "dense", 1)


def test_the_benchmark_gains_one_configuration_one_cell_and_two_readers():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # found by name, and after the entries the parent had: a later PR's appended entry must not fail this
    index = lambda entries, name: [e["name"] for e in entries].index(name)
    config = bench["configs"][index(bench["configs"], "keye-vl2-30b-a3b-int8")]
    assert index(bench["configs"], config["name"]) > index(bench["configs"], "phi-4-mini-flash-int8")
    assert config["reduced"] == CONFIG["reduced"] and config["source"] == CONFIG["source"]
    assert index(bench["workloads"], CELL) > index(bench["workloads"], "phi-4-mini-flash.reason")
    cell = bench["workloads"][index(bench["workloads"], CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "keye-vl2-30b-a3b-int8", "longctx-closed", 1)
    first = index(bench["per_layer"], "dsa_kv_read_share")
    assert first > index(bench["per_layer"], "window_skip_share")
    assert [m["name"] for m in bench["per_layer"][first:first + 2]] == ["dsa_kv_read_share", "dsa_busy_share"]
    for m in bench["per_layer"][first:first + 2]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # ops_bytes.py would read the dense width 6,144 as the experts': its readers keep to the accepted cells
    for name in ("decode_hbm_roofline_share", "moe_decode_hbm_roofline_share"):
        assert CELL not in by_name[name]["workloads"]
    mix = json.loads((BENCH / "traffic" / "longctx-closed.json").read_text())
    assert (mix["loop"], mix["schedule_seed"], mix["ramp_s"], mix["max_total_tokens"]) == ("closed", 36, 8, 12700)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 4096, "max": 12288}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 1.0, "seed_per_request": True}
    assert json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"] == 12


def test_byte_counts_by_hand():
    # one layer, int8 matrices: q and o 2048 x 4096, k and v 2048 x 512; an expert three matrices of 2048 x 768
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    expert = 3 * 2048 * 768
    assert (attention, expert, 128 * expert) == (18_874_368, 4_718_592, 603_979_776)
    # the indexer: int8 query projection 2048 x (16 x 64); bf16 key 2048 x 64 and weights 2048 x 16; bf16 router 2048 x 128
    indexer = 2048 * 1024 + 2 * (2048 * 64 + 2048 * 16)
    router = 2 * 2048 * 128
    layer = attention + 128 * expert + indexer + router
    assert (indexer, router, layer) == (2_424_832, 524_288, 625_803_264)
    tree = 8 * layer + 151_936 * 2048 * 2 + 151_936 * 2048          # + bf16 embedding + int8 head
    assert tree == 5_939_920_896 and "5.96 GB" in CONFIG["deployment"]   # the build adds scales and norms: 5,955,546,624
    token = dsa_bytes.cache_token_bytes(CONFIG)
    assert token == {"kv": 2 * 4 * 128 * 2, "index": 64 * 2} and sum(token.values()) == 2_176
    pool = 12 * 12_800 * 8 * 2_176
    assert pool == 2_673_868_800 and "2.67 GB" in CONFIG["deployment"]
    s = dsa_bytes.sizes(CONFIG)
    assert (s["S"], s["block"], s["S_tiles"], s["tokens"], s["topk"]) == (12_800, 1024, 13_312, 524, 2048)


def test_the_two_readers_on_hand_built_sources():
    recs = [{"kind": "decode", "steps": 8, "dsa_context_tokens": 6_000_000.0, "dsa_kv_tokens_read": 1_572_864.0},
            {"kind": "mixed", "steps": 1, "dsa_context_tokens": 30_000_000.0, "dsa_kv_tokens_read": 31_000_000.0},
            {"kind": "decode", "steps": 8},                          # a record without the counts: passed over
            {"kind": "prefill", "dsa_context_tokens": 5.0, "dsa_kv_tokens_read": 5.0}]
    src = {"config": CONFIG, "timeline": recs, "trace": {"busy_s": 2.0, "dsa_ops": {"dsa_s": 0.8, "dsa_events": 90}}}
    assert reader("dsa_kv_read_share").compute(src) == 100.0 * 32_572_864.0 / 36_000_000.0
    assert reader("dsa_busy_share").compute(src) == 40.0
    # nothing to read: a program without the counters or the operations (the parent commit), another configuration
    assert reader("dsa_kv_read_share").compute({**src, "timeline": recs[2:3]}) is None
    assert reader("dsa_busy_share").compute({**src, "trace": {"busy_s": 2.0, "dsa_ops": {"dsa_s": 0.0, "dsa_events": 0}}}) is None
    assert reader("dsa_busy_share").compute({**src, "trace": None}) is None
    # every part has to find an event: a program whose TILE is no longer the reader's finds no tile
    # operation, and the reader then says nothing rather than the one-query rows' share alone
    counted = {"dsa_s": 0.8, "dsa_events": 90, "events_by_part": {"indexer": 20, "row": 40, "tile": 30}}
    assert reader("dsa_busy_share").compute({**src, "trace": {"busy_s": 2.0, "dsa_ops": counted}}) == 40.0
    lame = {**counted, "events_by_part": {"indexer": 20, "row": 40, "tile": 0}}
    assert reader("dsa_busy_share").compute({**src, "trace": {"busy_s": 2.0, "dsa_ops": lame}}) is None
    other = json.loads((BENCH / "configs" / "smallthinker-21b-a3b-int8.json").read_text())
    assert reader("dsa_busy_share").compute({**src, "config": other}) is None


def test_the_stages_operations_are_known_by_their_own_shapes():
    import re

    pat = re.compile(dsa_bytes.dsa_pattern(CONFIG))
    # event names as a v5e trace carries them: the HLO instruction with its operands' shapes
    yes = ["%fusion.24 = f32[12,100,128]{2,1,0} fusion(bf16[12,100,64,128]{3,2,1,0} %bitcast.284, f32[12,16]{1,0} %w, bf16[12,16,64]{2,1,0} %qi)",
           "%fusion.1 = bf16[1200,64,128]{2,1,0} fusion(bf16[9600,64,128]{2,1,0} %pool, s32[2048]{0} %pad_clamp_fusion)",   # a row's pages of indexer keys
           "%fusion.276 = bf16[9600,64,128]{2,1,0} fusion(bf16[9600,64,128]{2,1,0} %pool, s32[12]{0} %w_idx, bf16[12,64]{0,1} %ki)",  # the key's write
           "%multiply_convert_fusion.4 = f32[12,1024]{1,0} fusion(s8[8,2048,1024]{2,1,0} %wq_idx, s32[] %li, bf16[12,2048]{1,0} %h)",
           "%convert_reduce_fusion.13 = (f32[524]{0}, bf16[524,64]{0,1}) fusion(bf16[8,2048,64]{1,2,0} %wk_idx, s32[] %li, bf16[524,2048]{1,0} %h)",
           "%sort.17 = (f32[12,12800]{1,0}, s32[12,12800]{1,0}) sort(f32[12,12800]{1,0} %fusion.34, s32[12,12800]{1,0} %iota.37), dimensions={1}, is_stable=true",
           "%slice_compare_fusion.1 = pred[12,2048]{1,0} fusion(s32[12,12800]{1,0} %idx, s32[12]{0} %lengths)",
           "%fusion.2 = bf16[24576,512]{1,0} fusion(bf16[1228800,512]{1,0} %k, s32[24576]{0} %broadcast_clamp_fusion)",   # the token gather
           "%fusion.40 = f32[12,4,8,2048]{3,2,1,0} fusion(bf16[12,4,8,128]{3,2,1,0} %q, bf16[12,2048,4,128]{3,2,1,0} %kk)",
           "%fusion.422 = f32[128,8,128]{2,0,1} fusion(f32[128,16]{0,1} %wit, bf16[128,16,64]{0,2,1} %qit, bf16[8,64,128]{2,1,0} %fusion.421)",
           "%fusion.254 = (s32[128,13312]{0,1}, pred[128,13312]{0,1}) fusion(u32[128,13312]{0,1} %keys, u32[128]{0} %kth)",
           "%compare_select_fusion.48 = u32[128]{0} fusion(s32[128]{0} %count, u32[128]{0} %t, pred[] %ok, u32[] %bit)",
           "%fusion.426 = bf16[8,128,512]{2,1,0} fusion(bf16[9600,128,512]{2,1,0} %pages_k, s32[1024]{0} %pad_clamp_fusion.20)",
           "%fusion.77 = f32[4,8,128,1024]{3,2,1,0} fusion(bf16[128,4,8,128]{3,2,1,0} %qg, bf16[1024,4,128]{2,1,0} %kk)",
           "%fusion.430 = f32[4,8,128,128]{3,2,1,0} fusion(f32[4,8,128]{2,1,0} %l, f32[4,8,128]{2,1,0} %max.74, pred[128,1024]{0,1} %mk)",
           "%fusion.418 = s32[652]{0} fusion(s32[652]{0} %walked, s32[] %q0, s32[] %nblk)",
           "%dsa_select.1 = s32[12,2048]{1,0} custom-call(f32[12,12800]{1,0} %scores)"]
    no = ["%fusion.800 = bf16[12,2048]{1,0} fusion(s8[8,2048,4096]{2,1,0} %w, s32[] %k)",       # the stream at hidden 2,048
          "%fusion.801 = bf16[524,2048]{1,0} fusion(bf16[524,4096]{1,0} %attn, s8[8,4096,2048]{2,1,0} %wo)",
          "%while.100 = (s32[], bf16[524,2048]{1,0}, bf16[1228800,512]{1,0}, bf16[9600,64,128]{2,1,0}) while((s32[], bf16[9600,64,128]{2,1,0}) %t)",  # the layer loop
          "%cond.3 = (bf16[524,32,128]{2,1,0}, s32[524]{0}) conditional(s32[] %short, (bf16[9600,64,128]{2,1,0}, f32[12,13312]{1,0}) %operands)",  # a conditional: its branch's operations are events of their own
          "%fusion.172 = (f32[12,1,128]{2,0,1}, f32[12,1,128]{2,0,1}) fusion(f32[12,1,64]{2,0,1} %angles)",   # the rotary's table: 64 = half a head
          "%fusion.173 = bf16[524,4,128]{2,1,0} fusion(f32[524,64]{1,0} %cos, f32[524,64]{1,0} %sin, bf16[524,512]{1,0} %k)",
          "%moe_gate_up_gmm.3 = bf16[4192,768]{1,0} custom-call(%a, %b)",
          "%ragged_paged_attention_tpu.2 = bf16[652,32,128]{2,1,0} custom-call(s32[12,100]{1,0} %table, bf16[652,32,128]{2,1,0} %pad.12)",  # another model's kernel
          "%fusion.9 = f32[12,151936]{1,0} fusion(bf16[12,2048]{1,0} %x, s8[2048,151936]{1,0} %head)"]
    assert all(pat.search(t) for t in yes) and not any(pat.search(t) for t in no)
    # by part: the tile's shapes follow the program's TILE and BLOCK_TOKENS, which this file repeats
    from inference_gateway_tpu.ops import sparse_attention

    assert (dsa_bytes.TILE, dsa_bytes.BLOCK) == (sparse_attention.TILE, sparse_attention.BLOCK_TOKENS)
    parts = {name: re.compile(dsa_bytes.NOT_A_BODY + ".*(?:" + "|".join(shapes) + ")")
             for name, shapes in dsa_bytes.dsa_shapes(CONFIG).items()}
    assert sorted(parts) == ["indexer", "row", "tile"]
    assert all(any(part.search(t) for part in parts.values()) for t in yes[:-1])   # but the kernel's name
    assert parts["tile"].search(yes[9]) and parts["row"].search(yes[5]) and parts["indexer"].search(yes[3])
    assert not any(part.search(t) for part in parts.values() for t in no)
    assert dsa_bytes._union_s([(0.0, 1.0), (0.2, 0.4), (2.0, 2.5), (2.4, 3.0)]) == 2.0


def test_reference_child_says_ok_on_probes_past_topk_of_a_tiny_engine(tmp_path):
    """Probes of 85-225 prompt tokens against topk 24, served through mixed
    steps of 64 tokens and decode chunks; then ``reference.py --rehearse-cpu``
    as ``run.py`` starts it, on the file as it is."""
    rng = np.random.default_rng(36)
    from inference_gateway_tpu.serving.scheduler import Scheduler

    scheduler = Scheduler(serving_engine(CONFIG["rehearsal"]))
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
    assert out.returncode == 0 and result["ok"], (result, out.stderr.decode()[-2000:])
    assert result["comparisons"] == 24 and result["platform"] == "cpu"
    # At the tiny size both sides round the same int8 weights' products to bf16 over two layers, and the
    # seeded build makes the chosen set show (ops/quant.seeded_std): measured 0.035 / 0.0078.
    assert result["max_abs_diff"] < 0.1 and result["mean_abs_diff"] < 0.03, result
    # The control, through the same child: the file with a topk past every probe, so the reference attends
    # over every key where the served path selected. Measured 0.250 / 0.091: 12 times the mean; at the
    # published widths on the chip the same control reads not correct (PERF.md section 6, PR 36).
    rehearsal = CONFIG["rehearsal"]
    model = {**rehearsal["model"], "sa_config": {**rehearsal["model"]["sa_config"], "topk": 10 ** 6}}
    control_file = tmp_path / "control.json"
    control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**rehearsal, "model": model}}))
    _, control = child(control_file)
    assert control["mean_abs_diff"] > 5 * result["mean_abs_diff"] and control["max_abs_diff"] > 0.1, control
