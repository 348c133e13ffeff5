"""The Phi-4-mini-flash configuration's files (PR 32): the source's keys
unchanged, nothing reduced, every assumed size stated, the byte plan and
``hybrid_bytes.py``'s count against sums by hand, and the three new readers on
hand-built sources."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import hybrid_bytes
import reference

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CONFIG = json.loads((BENCH / "configs" / "phi-4-mini-flash-int8.json").read_text())
CELL = "phi-4-mini-flash.reason"
# config.json of microsoft/Phi-4-mini-flash-reasoning as the catalog holds it
SOURCE = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
          "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
          "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
          "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
          "vocab_size": 200064}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_sources_keys_reduces_nothing_and_states_what_it_assumed():
    for key, value in SOURCE.items():
        assert CONFIG[key] == value and type(CONFIG[key]) is type(value), key
    assert CONFIG["reduced"] == []
    for key, value in (("mamba_d_state", 16), ("mamba_d_conv", 4), ("mamba_expand", 2), ("mamba_dt_rank", 160)):
        assert CONFIG[key] == value and str(value) in CONFIG["assumed"][key]
    for said in ("layer_kinds", "position_encoding", "differential_attention", "attention_bias", "window", "norm",
                 "weights", "head"):
        assert CONFIG["assumed"][said]
    assert CONFIG["reference"] == "sambay_diff" and CONFIG["deployment_sizes"]["tensor_parallel"] == 1
    ref = reference.load_reference(CONFIG["reference"])
    for model in (CONFIG, CONFIG["rehearsal"]["model"]):
        assert ref.sizes(model)["num_hidden_layers"] % 4 == 0
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == (
        "phi-4-mini-flash", "int8", "bfloat16", "dense", 1)


def test_the_benchmark_gains_one_configuration_one_cell_and_three_readers():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["configs"][-1]["name"] == "phi-4-mini-flash-int8" and bench["configs"][-1]["reduced"] == []
    assert bench["configs"][-1]["source"] == CONFIG["source"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "phi-4-mini-flash-int8", "reason-closed", 1)
    accepted = [w["name"] for w in bench["workloads"][:-1]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "ssm_busy_share", "hybrid_decode_hbm_roofline_share", "window_skip_share"]
    for m in bench["per_layer"][-3:]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
    # ops_bytes.py counts uniform GQA layers: the accepted roofline share keeps to the accepted cells
    assert by_name["decode_hbm_roofline_share"]["workloads"] == accepted
    mix = json.loads((BENCH / "traffic" / "reason-closed.json").read_text())
    assert (mix["loop"], mix["schedule_seed"], mix["ramp_s"], mix["max_total_tokens"]) == ("closed", 32, 8, 1975)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.7, "min": 32, "max": 640}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 640, "sigma": 0.5, "min": 128, "max": 1280}
    assert mix["sampling"] == json.loads((BENCH / "traffic" / "batch-closed.json").read_text())["sampling"]
    assert json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"] == 64


def test_byte_counts_by_hand():
    # ISSUE 32's sums: the matrices a layer kind holds
    mlp, mixer, self_attn, gmu, cross = 78_643_200, 41_123_840, 19_660_800, 26_214_400, 13_107_200
    assert mlp == 2560 * 20480 + 10240 * 2560 and self_attn == 2560 * 5120 + 2560 * 2560
    assert mixer == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    layers = 32 * mlp + 9 * mixer + 9 * self_attn + 7 * gmu + 7 * cross
    assert layers == 3_338_895_360 and layers + 200_064 * 2560 == 3_851_059_200
    # as served: W_x and W_dt (1,802,240 a mixer) and the tied head in bf16, the rest int8
    plain = 9 * (5120 * 192 + 160 * 5120)
    assert hybrid_bytes.weight_bytes_per_step(CONFIG) == (layers - plain) + 2 * plain + 2 * 200_064 * 2560 == 4_379_443_200
    assert hybrid_bytes.cache_token_bytes(CONFIG) == 2 * 20 * 64 * 2 == 5_120
    assert hybrid_bytes.state_bytes_per_row(CONFIG) == 9 * (327_680 + 61_440) == 3_502_080
    # a row of 650 tokens: 8 windowed layers see 512, the full layer and its 7 readers 650; state read and written
    assert hybrid_bytes.row_bytes(CONFIG, 650) == (8 * 512 + 8 * 650) * 5_120 + 2 * 3_502_080 == 54_599_680
    assert hybrid_bytes.row_bytes(CONFIG, 300) == 16 * 300 * 5_120 + 2 * 3_502_080
    step = hybrid_bytes.step_bytes(CONFIG, 64, 64 * 650, 64 * 512)
    assert step == 4_379_443_200 + 64 * 54_599_680 == 7_873_822_720      # ISSUE 32: "about 7.9 GB a step"
    # the deployment's plan: 9 caching layers, 64 slots of 2,048 tokens, and the state store
    pool, store = 64 * 2048 * 9 * 5_120, 64 * 3_502_080
    assert pool == 6_039_797_760 and store == 224_133_120
    assert 10.6e9 < 4_379_443_200 + pool + store < 10.7e9


def request(first, last, prompt, tokens):
    return SimpleNamespace(first_content_t=first, last_content_t=last, content_chars=tokens,
                           usages=[{"prompt_tokens": prompt}])


def test_live_rows_integrates_the_window_exactly():
    reqs = [request(0.0, 10.0, 100, 1000),     # 100 -> 1100 tokens: crosses 512 at t = 4.12
            request(2.0, 4.0, 600, 100),       # always past the window
            request(20.0, 30.0, 50, 10),       # outside the interval
            SimpleNamespace(first_content_t=None, last_content_t=None, content_chars=0, usages=[])]
    rows, whole, seen = hybrid_bytes.live_rows(reqs, 0.0, 10.0, 512)
    assert abs(rows - (10 + 2) / 10) < 1e-9
    assert abs(whole - (10 * 600 + 2 * 650) / 10) < 1e-9
    assert abs(seen - (4.12 * (100 + 512) / 2 + 5.88 * 512 + 2 * 512) / 10) < 1e-9


def sources(ssm_ops, records, requests=()):
    return {"config": CONFIG, "chip": {"hbm_bytes_per_s": 819e9}, "timeline": records,
            "all_requests": list(requests),
            "trace": {"busy_s": 2.0, "window_s": 3.0, "mono_start": 100.0, "decode_chunk_s": 0.104, "ssm_ops": ssm_ops}}


def test_the_three_readers_on_hand_built_sources():
    recs = [{"kind": "decode", "steps": 8, "window_pages_read": 2400.0, "context_pages": 3000.0},
            {"kind": "decode", "steps": 8, "window_pages_read": 2000.0, "context_pages": 3000.0},
            {"kind": "mixed", "steps": 1, "window_pages_read": 1.0, "context_pages": 9000.0},
            {"kind": "decode", "steps": 8}]                                # a record without the counts
    reqs = [request(90.0, 110.0, 400, 2000)] * 64                         # 64 rows, all past the window
    src = sources({"ssm_s": 0.3, "ssm_events": 900}, recs, reqs)
    assert reader("ssm_busy_share").compute(src) == 100.0 * 0.3 / 2.0
    assert abs(reader("window_skip_share").compute(src) - 100.0 * (1 - 4400 / 6000)) < 1e-9
    # contexts grow from 1,400 to 1,700 over the capture: mean 1,550 a row; a step is 104 ms / 8
    want = 100.0 * (hybrid_bytes.step_bytes(CONFIG, 64, 64 * 1550, 64 * 512) / 819e9) / 0.013
    got = reader("hybrid_decode_hbm_roofline_share").compute(src)
    assert abs(got - want) < 1e-6 and 90 < want < 100
    # nothing to read: a program without the layer, the counters, or another configuration (the parent commit)
    assert reader("ssm_busy_share").compute(sources({"ssm_s": 0.0, "ssm_events": 0}, recs)) is None
    assert reader("window_skip_share").compute(sources(None, [r for r in recs if "context_pages" not in r])) is None
    dense = {**src, "config": json.loads((BENCH / "configs" / "mistral-7b-int8.json").read_text())}
    assert reader("hybrid_decode_hbm_roofline_share").compute(dense) is None
    assert reader("ssm_busy_share").compute(dense) is None
    for name in ("ssm_busy_share", "hybrid_decode_hbm_roofline_share"):
        assert reader(name).compute({**src, "trace": None}) is None


def test_state_operations_are_known_by_the_stores_shapes():
    import re

    pat = re.compile(hybrid_bytes.ssm_pattern(CONFIG))
    yes = ["%while.52 = (s32[]{:T(128)}, f32[64,5120,16]{1,2,0:T(8,128)S(1)}, f32[576,5120]{1,0}) while(%tuple)",
           "%fusion.9 = f32[64,5120,16]{1,2,0} fusion(f32[9,64,5120,16]{2,3,1,0} %gte, s32[] %k)",
           "%fusion.4 = f32[64,3,5120]{2,1,0} fusion(f32[64,4,5120]{2,1,0} %concatenate.1)",
           "%fusion.7 = f32[5120]{0} fusion(f32[5120,16]{1,0} %state, f32[16]{0} %c)",
           "%ssm_scan.3 = f32[64,5120]{1,0} custom-call(%a)"]
    no = ["%while.100 = (s32[], bf16[64,2560]{1,0}, f32[9,64,5120,16]{2,3,1,0}, f32[9,64,3,5120]{3,2,1,0}) while(%t)",
          "%fusion.800 = bf16[64,20480]{1,0} fusion(s8[32,2560,20480]{2,1,0} %w, s32[] %k)",
          "%paged_attention_tpu.25 = bf16[64,40,128]{2,1,0} custom-call(%q)"]
    assert all(pat.search(t) for t in yes) and not any(pat.search(t) for t in no)
    assert hybrid_bytes._union_s([(0.0, 1.0), (0.2, 0.4), (2.0, 2.5), (2.4, 3.0)]) == 2.0
