"""The Trinity-Mini configuration's files (PR 41): the catalog row's keys
unchanged but the depth and ``layer_types`` (cut with it), the cut and what was
assumed stated, byte counts against arithmetic by hand, the two new readers on
hand-built sources, the entries found by name, and the reference child on probes
of a tiny engine whose window is shorter than its probes."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import numpy as np

import afmoe_bytes
import reference
from test_reference_child import serve_probe

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CELL = "trinity-mini.long-context"
CONFIG_FILE = BENCH / "configs" / "trinity-mini-int8.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
PERIOD = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
# `config` of the catalog's row (architectures.jsonl, Trinity-Mini), every key but the two that were cut
SOURCE = {"global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
          "intermediate_size": 6144, "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
          "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
          "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
          "num_experts_per_tok": 8, "num_key_value_heads": 4, "num_limited_groups": 1, "num_shared_experts": 1,
          "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
          "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False,
          "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_keeps_the_rows_keys_and_states_its_cut_and_what_it_assumed():
    for key, value in SOURCE.items():
        assert CONFIG[key] == value and type(CONFIG[key]) is type(value), key
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    assert CONFIG["num_hidden_layers"] == 8 and CONFIG["published"]["num_hidden_layers"] == 32
    assert CONFIG["layer_types"] == PERIOD * 2          # the first 8 of the source's 32: two whole periods
    # the five mechanisms config.json has no key for, and every size this file set
    for said in ("attention_output_gate", "qk_norm", "post_norms", "selection_bias", "global_layers_rope",
                 "shared_expert", "dense_layers", "grouping", "mup_enabled", "intermediate_size", "weights",
                 "tokenizer", "context"):
        assert CONFIG["assumed"][said]
    assert CONFIG["assumed_mechanisms"] == {"attention_output_gate": True, "qk_norm": True, "post_norms": True,
                                            "selection_bias": "selection", "global_layers_rope": False}
    assert "first of four pipeline stages of 8" in CONFIG["deployment"] and "tensor_parallel 1" in CONFIG["deployment"]
    assert CONFIG["sidecar_args"] == ["--max-slots", "32", "--max-seq-len", "12800", "--page-size", "128",
                                      "--quantize", "int8", "--attention", "paged", "--dtype", "bfloat16"]
    assert CONFIG["reference"] == "afmoe" and CONFIG["deployment_sizes"]["tensor_parallel"] == 1
    probes = CONFIG["probes"]
    assert probes["before_window_chars"] == [3000] and probes["in_window_chars"] == [1500, 2500, 5000, 9000]
    window = CONFIG["sliding_window"]
    assert sum(c + 25 + 8 <= window for c in probes["in_window_chars"]) == 1 and probes["max_tokens"] == 8
    tiny = CONFIG["rehearsal"]
    assert tiny["preset"] == "trinity-test-tiny" and tiny["model"]["layer_types"] == PERIOD * 2
    assert all(c > tiny["model"]["sliding_window"] for c in tiny["probes"]["before_window_chars"]
               + tiny["probes"]["in_window_chars"])
    ref = reference.load_reference(CONFIG["reference"])
    for model in (CONFIG, tiny["model"]):
        sizes = ref.sizes(model)
        assert sizes["layer_types"] == tuple(PERIOD * 2) and sizes["selection_bias"] == "selection"
        assert (sizes["num_dense_layers"], sizes["num_shared_experts"]) == (2, 1)
        assert sizes["route_scale"] == (2.826 if model is CONFIG else 1.414)      # sqrt(top-k) at either size
    cfg = reference.engine_config(CONFIG["preset"], CONFIG["sidecar_args"])
    assert (cfg.model, cfg.quantize, cfg.dtype, cfg.attention, cfg.max_slots) == (
        "trinity-mini-stage8", "int8", "bfloat16", "dense", 1)


def test_the_benchmark_gains_one_configuration_one_cell_and_two_readers_after_keyes():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # found by name, and after the entries the parent had: a later PR's appended entry must not fail this
    index = lambda entries, name: [e["name"] for e in entries].index(name)
    config = bench["configs"][index(bench["configs"], "trinity-mini-int8")]
    assert index(bench["configs"], config["name"]) > index(bench["configs"], "keye-vl2-30b-a3b-int8")
    assert config["reduced"] == CONFIG["reduced"] and config["source"] == CONFIG["source"]
    assert config["file"] == "bench/configs/trinity-mini-int8.json"
    assert index(bench["workloads"], CELL) > index(bench["workloads"], "keye-vl2-30b-a3b.long-context")
    cell = bench["workloads"][index(bench["workloads"], CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "trinity-mini-int8", "longctx-closed", 1)
    first = index(bench["per_layer"], "swa_page_skip_share")
    assert first > index(bench["per_layer"], "dsa_busy_share")
    assert [m["name"] for m in bench["per_layer"][first:first + 2]] == [
        "swa_page_skip_share", "afmoe_step_hbm_roofline_share"]
    for m in bench["per_layer"][first:first + 2]:
        r = reader(m["name"])
        assert m["workloads"] == [CELL] and (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # ops_bytes.py would read the dense width 6,144 as the experts': its readers keep to the accepted cells
    for name in ("decode_hbm_roofline_share", "moe_decode_hbm_roofline_share", "fetch_return_ms"):
        assert CELL not in by_name[name]["workloads"]
    # the cell is appended to the lists of the accepted readers it feeds, behind the cells the parent had
    accepted = [w["name"] for w in bench["workloads"][:index(bench["workloads"], CELL)]]
    for name in ("ttft_p50_ms.batch", "mixed_step_host_ms.batch", "host_starved_share", "idle_unexplained_share",
                 "start_import_s", "start_engine_build_s", "moe_busy_share", "attn_kernel_busy_share"):
        listed = by_name[name]["workloads"]
        assert all(listed.index(CELL) > listed.index(w) for w in accepted if w in listed), name
    # The cell makes no `decode` record in its window (its prompt chunks saturate: every step is a mixed step;
    # PERF.md section 6, PR 41), so the three accepted readers of `decode` records alone do not list it: the
    # two that listed no cells now list the seven the parent had, which the contract takes as no change.
    assert CELL not in by_name["experts_read_mean"]["workloads"]
    # Nor do the four readers of what the device was starved BY: in this cell it never is (`host_starved_share`
    # 0.0, device idle 0.035%: no record carries `starved_by`, the capture holds no gap of 50 us), so they find
    # nothing to read, and a listed metric that a traced line lacks is refused.
    for name in ("idle_unspanned_share", "starved_slot_share", "starved_delivery_share", "starved_unspanned_share"):
        assert CELL not in by_name[name]["workloads"], name
    for name in ("decode_step_ms", "decode_batch_mean"):
        assert by_name[name]["workloads"] == accepted[:7] and len(accepted) >= 7, name
    mix = json.loads((BENCH / "traffic" / "longctx-closed.json").read_text())       # the accepted file, unedited
    assert (mix["loop"], mix["schedule_seed"], mix["ramp_s"], mix["max_total_tokens"]) == ("closed", 36, 8, 12700)
    clients = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["clients"]
    assert clients == CONFIG["deployment_sizes"]["max_slots"]                       # one client a slot


def test_byte_counts_by_hand():
    # one layer, int8 matrices: q, o and the gate 2048 x 4096 each, k and v 2048 x 512 each
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    expert = 3 * 2048 * 1024
    assert (attention, expert, 128 * expert) == (27_262_976, 6_291_456, 805_306_368)
    assert (afmoe_bytes.attention_bytes(CONFIG), afmoe_bytes.expert_bytes(CONFIG)) == (attention, expert)
    router = 2 * 2048 * 128                                                        # bf16
    expert_layer = attention + 128 * expert + expert + router
    dense_layer = attention + 3 * 2048 * 6144
    assert (router, expert_layer, dense_layer) == (524_288, 839_385_088, 65_011_712)
    embedding, head = 200_192 * 2048 * 2, 200_192 * 2048
    assert (embedding, head) == (819_986_432, 409_993_216)
    tree = 2 * dense_layer + 6 * expert_layer + embedding + head
    assert tree == 6_396_313_600 and "6.40 GB" in CONFIG["deployment"]             # the build adds scales and norms
    # what every step reads whatever its rows chose: the tree less the routed experts and the embedding
    fixed = afmoe_bytes.fixed_weight_bytes(CONFIG)
    assert fixed == tree - embedding - 6 * 128 * expert == 744_488_960
    page = afmoe_bytes.page_bytes(CONFIG)
    assert page == 128 * 2 * 4 * 128 * 2 == 262_144
    pool = 32 * 12_800 * 8 * (page // 128)
    assert pool == 6_710_886_400 and "6.71 GB" in CONFIG["deployment"]
    assert "13.11 GB" in CONFIG["deployment"] and round((tree + pool) / 1e9, 2) == 13.11
    s = afmoe_bytes.sizes(CONFIG)
    assert (s["windowed"], s["full"], s["dense_layers"], s["E"], s["shared"]) == (6, 2, 2, 128, 1)
    # a step whose rows chose 111.5 distinct experts a layer, whose windowed layers read 600 of the 1,800 pages
    # their rows hold (300 a layer), and whose 2 global layers therefore hold 600
    record = {"kind": "mixed", "experts_read": 111.5, "window_pages_read": 600.0, "context_pages": 1800.0}
    want = fixed + 111.5 * 6 * expert + (600 + 1800 / 6 * 2) * page
    assert afmoe_bytes.step_bytes(CONFIG, record) == want == 5_268_045_824.0
    assert afmoe_bytes.step_bytes(CONFIG, {"kind": "mixed", "experts_read": 111.5}) is None


def test_the_two_readers_on_hand_built_sources():
    recs = [{"kind": "decode", "steps": 8, "start_ts": 10.0, "experts_read": 110.0, "window_pages_read": 4000.0,
             "context_pages": 16000.0},
            {"kind": "mixed", "steps": 1, "start_ts": 11.0, "experts_read": 128.0, "window_pages_read": 500.0,
             "context_pages": 2000.0},
            {"kind": "mixed", "steps": 1, "start_ts": 12.0, "experts_read": 120.0, "window_pages_read": 700.0,
             "context_pages": 2400.0},
            {"kind": "mixed", "steps": 1, "start_ts": 13.0, "experts_read": 124.0, "window_pages_read": 600.0,
             "context_pages": 2200.0},
            {"kind": "decode", "steps": 8, "start_ts": 14.0},                     # a record without the counts
            {"kind": "prefill", "start_ts": 15.0, "window_pages_read": 5.0, "context_pages": 5.0}]
    capture = {"capture_epoch_ns": [10.5e9, 13.5e9]}
    src = {"config": CONFIG, "timeline": recs, "chip": {"hbm_bytes_per_s": 819e9},
           "status": {"jax_trace": capture}, "trace": {"busy_s": 2.0, "mixed_step_s": 0.012}}
    skip = reader("swa_page_skip_share")
    assert skip.compute(src) == 100.0 * (1.0 - 5800.0 / 22600.0)                  # decode AND mixed records
    assert skip.compute({**src, "timeline": recs[4:]}) is None                     # the parent commit: no counter
    roof = reader("afmoe_step_hbm_roofline_share")
    counts = sorted(afmoe_bytes.step_bytes(CONFIG, r) for r in recs[1:4])
    assert roof.compute(src) == 100.0 * (counts[1] / 819e9) / 0.012               # the median step's bytes
    assert 40.0 < roof.compute(src) < 100.0
    # nothing to read: no trace, no mixed-step program in it, no capture window, a record without a counter,
    # no mixed record in the capture, another configuration
    assert roof.compute({**src, "trace": None}) is None
    assert roof.compute({**src, "trace": {"busy_s": 2.0}}) is None
    assert roof.compute({**src, "status": {}}) is None
    lame = [dict(recs[1]), {"kind": "mixed", "steps": 1, "start_ts": 12.0, "experts_read": 120.0}]
    assert roof.compute({**src, "timeline": lame}) is None
    assert roof.compute({**src, "timeline": recs[:1]}) is None
    other = json.loads((BENCH / "configs" / "keye-vl2-30b-a3b-int8.json").read_text())
    assert roof.compute({**src, "config": other}) is None


def test_reference_child_on_probes_past_the_window_of_a_tiny_engine(tmp_path):
    """Probes of 85-225 prompt tokens against a window of 16, served through mixed
    steps of 64 tokens and decode chunks by the rehearsal's own engine; then
    ``reference.py --rehearse-cpu`` as ``run.py`` starts it, on the file as it is:
    the UNEDITED child finds every leaf the reference declares in the program's
    two stacks and reads the right one at each index."""
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    run_cfg = CONFIG["rehearsal"]
    args = dict(zip(run_cfg["sidecar_args"][::2], run_cfg["sidecar_args"][1::2]))
    engine = Engine(EngineConfig(
        model=run_cfg["preset"], quantize=args["--quantize"], dtype=args["--dtype"], attention=args["--attention"],
        page_size=int(args["--page-size"]), max_slots=int(args["--max-slots"]), max_seq_len=int(args["--max-seq-len"]),
        mixed_step=True, mixed_step_tokens=int(run_cfg["sidecar_env"]["SERVING_MIXED_STEP_TOKENS"])))
    assert engine.mixed_ok
    rng = np.random.default_rng(41)
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
    # At this size a router score within bf16's rounding of the cut swaps one of a token's TWO experts (a
    # quarter of the sublayer's normed output; an eighth at the published top-8): measured over six pairs of
    # prompts 0.16-0.31 / 0.047-0.081 (tests/test_trinity.py), inside the child's limits with less room than
    # the chip's runs at the published widths have (a fifth of them).
    assert result["ok"] and result["mean_abs_diff"] < 0.1, result
    assert out.returncode == (0 if result["ok"] else 1)
    # A control through the same child: the file with the window dropped from the sliding layers.
    # Measured 6.9-10.2 / 3.7-4.9: NOT ok by both limits.
    model = {**run_cfg["model"], "sliding_window": 10 ** 9}
    control_file = tmp_path / "control.json"
    control_file.write_text(json.dumps({**CONFIG, "rehearsal": {**run_cfg, "model": model}}))
    out, control = child(control_file)
    assert out.returncode == 1 and control["ok"] is False, control
    assert control["mean_abs_diff"] > 5 * result["mean_abs_diff"] and control["max_abs_diff"] > 1.0, control
