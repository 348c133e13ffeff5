import importlib.util
import json
import random
from pathlib import Path

import pytest

import client
import ops_bytes
import stats
import trace_reduce
import traffic

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent


def load(path):
    return json.loads(Path(path).read_text())


# -- the trace reduction on a hand-built trace --------------------------------
SPEC = {"classes": [{"class": "attention_kernel", "regex": "paged_attn"},
                    {"class": "matmul", "regex": "fusion|dot"},
                    {"class": "other", "regex": "."}],
        "modules": {"decode_chunk": "decode_chunk"}}


def test_busy_union_merges_overlaps_and_nesting():
    assert trace_reduce.union_s([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4.0)
    assert trace_reduce.union_s([(0, 10), (2, 3)]) == pytest.approx(10.0)
    assert trace_reduce.union_s([]) == 0.0


def test_self_time_takes_children_out_of_their_parent():
    # a 10 s while loop holding a 3 s kernel and a 4 s fusion: 3 s of its own
    got = dict(trace_reduce.self_times([("while", 0.0, 10.0), ("paged_attn", 1.0, 3.0),
                                        ("fusion.1", 5.0, 4.0)]))
    assert got == {"while": pytest.approx(3.0), "paged_attn": pytest.approx(3.0),
                   "fusion.1": pytest.approx(4.0)}


def test_reduce_events_idle_share_class_sums_and_decode_step():
    ops = {"/device:TPU:0": [("while", 1.0, 4.0), ("paged_attn_kernel", 1.0, 1.0),
                             ("fusion.7", 2.0, 3.0), ("copy", 7.0, 1.0)]}
    mods = {"/device:TPU:0": [("jit_decode_chunk_fn(1)", 1.0, 4.0), ("jit_decode_chunk_fn(1)", 6.0, 2.0),
                              ("jit_mixed_step_fn(2)", 7.0, 1.0)]}
    out = trace_reduce.reduce_events(ops, mods, (0.0, 10.0), SPEC)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["busy_s"] == pytest.approx(5.0)            # [1,5] and [7,8]
    assert 100 * (1 - out["busy_s"] / out["window_s"]) == pytest.approx(50.0)
    assert out["class_s"]["attention_kernel"] == pytest.approx(1.0)
    assert out["class_s"]["matmul"] == pytest.approx(3.0)
    assert out["class_s"]["other"] == pytest.approx(1.0)  # the copy; the while has no time of its own
    assert sum(out["class_s"].values()) == pytest.approx(out["busy_s"])
    assert out["decode_chunk_s"] == pytest.approx(3.0)    # median of 4 and 2
    assert "mixed_step_s" not in out                       # SPEC names no such program
    assert sorted(out["gaps"]) == [(0.0, 1.0), (5.0, 2.0), (8.0, 2.0)]   # (start in the window, length)
    assert sorted(g[1] for g in out["gaps"]) == [pytest.approx(1.0), pytest.approx(2.0), pytest.approx(2.0)]


def test_committed_classes_compile_and_end_in_a_catch_all():
    spec = load(BENCH / "trace_classes.json")
    classes = trace_reduce.compile_classes(spec)
    assert classes[-1][0] == "other"
    assert trace_reduce.classify("_ragged_paged_attn_kernel", classes) == "attention_kernel"
    assert trace_reduce.classify("_paged_attn_kernel.3", classes) == "attention_kernel"
    assert trace_reduce.classify("zzz", classes) == "other"


# -- percentile and TPOT arithmetic ---------------------------------------------
def result(due, first, last, n, **kw):
    r = client.Result(index=0, trace_id="t", due_t=due, sent_t=due)
    r.first_content_t, r.last_content_t, r.content_chars = first, last, n
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def test_percentiles_interpolate_between_ranks():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert stats.percentile([], 50) is None
    assert stats.percentile([7], 95) == 7


def test_p95_needs_two_hundred_requests():
    assert stats.p95([1.0] * 199) is None
    assert stats.p95([1.0] * 200) == 1.0


def test_ttft_counts_from_the_due_moment_and_tpot_over_gaps():
    r = result(due=10.0, first=10.25, last=11.25, n=11)
    r.sent_t = 10.1                                   # the generator ran 100 ms late
    assert stats.ttft_ms(r) == pytest.approx(250.0)   # from when it was due, not when it left
    assert stats.tpot_ms(r) == pytest.approx(100.0)   # 1 s over 10 gaps
    assert stats.tpot_ms(result(0, 1, 1, 1)) is None
    assert stats.ttft_ms(result(0, None, None, 0)) is None


def test_mean_live_context_integrates_growth():
    r = result(0.0, 0.0, 10.0, 100, usages=[{"prompt_tokens": 50}])
    ctx, rows = stats.mean_live_context([r], 0.0, 10.0)
    assert ctx == pytest.approx(100.0) and rows == pytest.approx(1.0)   # 50 + mean growth of 50
    ctx, rows = stats.mean_live_context([r], 5.0, 15.0)
    assert ctx == pytest.approx(0.5 * 125.0) and rows == pytest.approx(0.5)


# -- the schedule is the mix's, the words are the seed's ---------------------------
def lengths(reqs):
    return [(len(r.messages[0]["content"]), r.max_tokens, r.due_s) for r in reqs]


@pytest.mark.parametrize("mix_name", ["chat-open", "rag-open"])
def test_open_loop_same_seed_same_requests_other_seed_same_schedule(mix_name):
    mix = load(BENCH / "traffic" / f"{mix_name}.json")
    a = traffic.open_loop(mix, 5.0, 40.0, 7)
    b = traffic.open_loop(mix, 5.0, 40.0, 7)
    c = traffic.open_loop(mix, 5.0, 40.0, 2**31 + 12345)
    assert a == b and a != c
    assert lengths(a) == lengths(c)                       # when, and how long: the mix's alone
    assert [r.messages for r in a] != [r.messages for r in c] and [r.seed for r in a] != [r.seed for r in c]
    assert 150 < len(a) < 250 and all(0 < r.due_s < 40.0 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    assert len(set(r.seed for r in a)) == len(a) and all(0 < r.seed < 2**31 for r in a)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= n <= hi and n + o + 25 <= 2000 for n, o, _ in lengths(a))
    assert set("".join(r.messages[0]["content"] for r in a[:5])) <= set(traffic.PRINTABLE)
    # a shorter window is a prefix of a longer one; a ramp is a path of its own
    assert lengths(traffic.open_loop(mix, 5.0, 20.0, 7)) == [x for x in lengths(a) if x[2] < 20.0]
    assert lengths(traffic.open_loop(mix, 5.0, 6.0, 7, stream="ramp")) != [x for x in lengths(a) if x[2] < 6.0]


def test_open_loop_gaps_are_plain_exponential():
    mix = load(BENCH / "traffic" / "chat-open.json")
    dues = [r.due_s for r in traffic.open_loop(mix, 10.0, 2000.0, 1)]
    gaps = [y - x for x, y in zip(dues, dues[1:])]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert mean == pytest.approx(0.1, rel=0.03) and cv == pytest.approx(1.0, rel=0.05)
    # not balanced: the arrivals in consecutive 2.4 s spans scatter as a Poisson count does
    counts = [sum(1 for d in dues if k * 2.4 <= d < (k + 1) * 2.4) for k in range(800)]
    m = sum(counts) / len(counts)
    assert sum((c - m) ** 2 for c in counts) / len(counts) == pytest.approx(m, rel=0.15)


def test_closed_loop_queue_is_the_same_work_for_every_seed():
    mix = load(BENCH / "traffic" / "batch-closed.json")
    a, c = traffic.closed_loop(mix, 288, 1), traffic.closed_loop(mix, 288, 2)
    assert len(a) == 288 and [r.index for r in a] == list(range(288))
    assert lengths(a) == lengths(c) and a != c and a == traffic.closed_loop(mix, 288, 1)


@pytest.mark.parametrize("name,base", [("ttft_p50_ms.batch", "ttft_p50_ms"),
                                       ("attn_kernel_busy_share.prefill", "attn_kernel_busy_share")])
def test_a_dotted_name_without_a_file_takes_the_reader_before_the_dot(name, base):
    import run
    assert run.load_reader("layers", name).compute is not None
    assert run.load_reader("layers", name).__doc__ == run.load_reader(
        "layers" if (BENCH / "layers" / f"{base}.py").is_file() else "end_to_end", base).__doc__


# -- SSE parsing on a canned stream --------------------------------------------------
def chunk(delta=None, finish=None, usage=None, cid="chatcmpl-abc"):
    doc = {"id": cid, "object": "chat.completion.chunk", "choices": (
        [] if usage else [{"index": 0, "delta": delta or {}, "finish_reason": finish}])}
    if usage:
        doc["usage"] = usage
    return b"data: " + json.dumps(doc).encode() + b"\n\n"


def canned(n_tokens=3, usage_tokens=3):
    return (chunk({"role": "assistant", "content": ""}) + chunk({"content": ""})
            + b"".join(chunk({"content": "x"}) for _ in range(n_tokens)) + chunk({}, "length")
            + chunk(usage={"prompt_tokens": 30, "completion_tokens": usage_tokens,
                           "total_tokens": 30 + usage_tokens}) + b"data: [DONE]\n\n")


def feed(raw, step, t0=100.0, window=(0.0, 1e9)):
    res = client.Result(index=0, trace_id="t", due_t=99.0, sent_t=99.0, status=200, max_tokens=3)
    parser, now = client.SSEParser(), t0
    for i in range(0, len(raw), step):
        for payload in parser.feed(raw[i:i + step]):
            client.apply_event(res, payload, now, window)
        now += 0.001
    assert parser.pending == b""
    return res


@pytest.mark.parametrize("step", [1, 7, 10_000])
def test_sse_counts_only_content_frames_whatever_the_packet_boundaries(step):
    res = feed(canned(), step)
    assert res.content_chars == 3 and res.content_frames == 3 and res.text == "xxx"
    assert res.role_frames == 1 and res.finishes == ["length"] and res.done_last
    assert res.first_content_t > 100.0 or step == 10_000   # never the role preamble's time
    assert stats.stream_fault(res, 30) is None


def test_first_token_time_is_not_the_role_preamble():
    parser = client.SSEParser()
    res = client.Result(index=0, trace_id="t", due_t=0.0, status=200)
    for now, raw in ((1.0, chunk({"role": "assistant", "content": ""})), (2.0, chunk({"content": ""})),
                     (3.0, chunk({"content": "a"})), (4.0, chunk({"content": "bc"}))):
        for payload in parser.feed(raw):
            client.apply_event(res, payload, now, (3.5, 10.0))
    assert res.first_content_t == 3.0 and res.last_content_t == 4.0
    assert res.content_chars == 3 and res.chars_in_window == 2 and res.max_gap_s == 1.0


@pytest.mark.parametrize("raw,why", [
    (canned(n_tokens=2), "content frames carried 2"),                      # a lost token
    (canned().replace(b"data: [DONE]\n\n", b""), "[DONE]"),
    (canned() + chunk({"content": "y"}), "after [DONE]"),
    (canned().replace(b'"length"', b'null'), "finish_reasons"),
    (canned(usage_tokens=4), "usage says 4"),
])
def test_malformed_streams_are_named(raw, why):
    assert why in stats.stream_fault(feed(raw, 64), 30)


def test_prompt_tokens_follow_the_template():
    tpl = load(BENCH / "configs" / "mistral-7b-int8.json")["chat_template"]
    assert stats.expected_prompt_tokens([{"role": "user", "content": "x" * 100}], tpl) == 125


# -- ops_bytes against hand arithmetic -------------------------------------------------
def test_ops_bytes_mistral():
    c = load(BENCH / "configs" / "mistral-7b-int8.json")
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336     # 218,103,808
    assert ops_bytes.layer_matmul_params(c) == per_layer == 218_103_808
    assert ops_bytes.weight_bytes_per_step(c) == 32 * per_layer + 4096 * 32000 == 7_110_393_856
    assert ops_bytes.kv_bytes_per_token(c) == 2 * 32 * 8 * 128 * 2 == 131_072
    assert 24 * 2048 * ops_bytes.kv_bytes_per_token(c) == 6 * 2**30
    assert ops_bytes.flops_per_token(c) == 2 * 7_110_393_856


def test_ops_bytes_qwen():
    c = load(BENCH / "configs" / "qwen2.5-7b-int8.json")
    per_layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944      # 233,046,016
    assert ops_bytes.layer_matmul_params(c) == per_layer == 233_046_016
    assert ops_bytes.weight_bytes_per_step(c) == 28 * per_layer + 3584 * 152064 == 7_070_285_824
    assert ops_bytes.kv_bytes_per_token(c) == 2 * 28 * 4 * 128 * 2 == 57_344
    assert 48 * 2048 * ops_bytes.kv_bytes_per_token(c) == int(5.25 * 2**30)


# -- BENCHMARK.json against the files it names, and the last line's keys ------------------
def test_every_name_in_benchmark_json_finds_its_files():
    b = load(ROOT / "BENCHMARK.json")
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    for c in b["configs"]:
        doc = load(ROOT / c["file"])
        assert doc["source"] == c["source"] and len(c["source"]) <= 200 and doc["reduced"] == c["reduced"]
    for w in b["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "cells" / f"{w['name']}.json").is_file()
        assert len(w["why"]) <= 200
    for section, kind in (("end_to_end", "end_to_end"), ("per_layer", "layers")):
        for m in b[section]:
            import run
            mod = run.load_reader(kind, m["name"])
            assert mod.UNIT == m["unit"] and callable(mod.compute)
            if section == "per_layer":
                assert m["moves"] in e2e
                if (BENCH / kind / f"{m['name']}.py").is_file():
                    assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]


def test_last_line_keys():
    run = (BENCH / "run.py").read_text()
    for key in ('"correct"', '"attempted"', '"failed"', '"metrics"', '"device"', '"platform"', '"kind"',
                '"count"', '"memory_peak_bytes"', '"busy_s"', '"window_s"', '"breakdown"', '"device_ops"',
                '"idle_gaps"'):
        assert key in run


def test_readers_return_nothing_when_there_is_nothing_to_read():
    empty = {"requests": [], "all_requests": [], "window": {"seconds": 10.0}, "access_log": {},
             "timeline": [], "trace": None, "status": {}, "config": {}, "chip": {}, "setup": {"setup_s": 1.0}}
    for kind in ("layers", "end_to_end"):
        for path in sorted((BENCH / kind).glob("*.py")):
            spec = importlib.util.spec_from_file_location("m", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            value = mod.compute(empty)
            assert value is None or path.stem == "setup_s", path.name
