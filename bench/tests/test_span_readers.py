"""The readers of the program's own step spans (PR 24), on hand-built sources:
a window of timeline records as the sidecar writes them, a capture that cuts a
record in two, and a parent-commit document whose records have no phases."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

T0 = 1_790_000_000.0  # epoch seconds of the window's opening


def reader(name):
    import run
    return run.load_reader("layers", name)


def mixed(start, starved=0.0, **phases):
    base = {"build": 0.5, "lock_wait": 0.0, "pages": 1.0, "assemble": 0.5, "upload_dispatch": 4.0,
            "fetch_wait": 50.0, "emit": 2.0}
    base.update(phases)
    dur = sum(base.values())
    return {"kind": "mixed", "start_ts": T0 + start, "ts": T0 + start + dur / 1e3, "duration_ms": dur,
            "steps": 1, "batch": 9, "tokens": 9, "starved_ms": starved, "inflight_at_dispatch": 0,
            "phases": base, "context_tokens": 4000}


def decode(start, submit, starved=0.0, inflight=1):
    return {"kind": "decode", "start_ts": T0 + start, "ts": T0 + start + 0.1, "duration_ms": 100.0,
            "steps": 8, "batch": 12, "tokens": 96, "starved_ms": starved, "inflight_at_dispatch": inflight,
            "submit_ts": T0 + submit, "context_tokens": 5000,
            "phases": {"build": 0.0, "upload_dispatch": 0.3, "fetch_wait": 95.0, "emit": 5.0}}


def parent_record(kind, start):
    """What the parent commit writes: no start_ts, no phases, no starved_ms."""
    return {"kind": kind, "ts": T0 + start, "duration_ms": 60.0, "steps": 1 if kind == "mixed" else 8,
            "batch": 9, "tokens": 9, "kv_utilization": 0.4, "queue_depth": 0}


def sources(timeline, status=None, trace=None, access=None, requests=()):
    return {"timeline": timeline, "status": status or {}, "trace": trace, "access_log": access or {},
            "requests": list(requests), "all_requests": list(requests),
            "window": {"seconds": 50.0, "epoch_open": T0}}


def test_mixed_step_host_ms_is_the_median_host_side_of_a_mixed_step_and_skips_the_fetch():
    tl = [mixed(1.0), mixed(2.0, upload_dispatch=6.0), mixed(3.0, upload_dispatch=10.0), decode(4.0, 3.8)]
    assert reader("mixed_step_host_ms").compute(sources(tl)) == pytest.approx(0.5 + 1.0 + 0.5 + 6.0 + 2.0)
    # the batch cell's entry has no file of its own: the same reader
    assert reader("mixed_step_host_ms.batch").compute(sources(tl)) == pytest.approx(10.0)


def test_host_starved_share_sums_every_kind_over_the_whole_window():
    tl = [mixed(1.0, starved=30.0), decode(2.0, 1.9, starved=20.0, inflight=0), decode(3.0, 2.0),
          {"kind": "prefill", "start_ts": T0 + 4, "ts": T0 + 4.1, "starved_ms": 450.0}]
    assert reader("host_starved_share").compute(sources(tl)) == pytest.approx(100 * 0.5 / 50.0)


def capture(lo, hi, **more):
    return {"jax_trace": {"start_epoch_ns": int((T0 + lo - 0.3) * 1e9), "stop_epoch_ns": int((T0 + hi + 2.5) * 1e9),
                          "capture_epoch_ns": [int((T0 + lo) * 1e9), int((T0 + hi) * 1e9)],
                          "python_tracer": True, **more}}


def test_idle_unexplained_share_counts_the_records_dispatched_inside_the_capture():
    trace = {"window_s": 3.0, "busy_s": 2.7}          # 0.3 s of idle in the capture
    tl = [mixed(19.0, starved=500.0),                  # before the capture: not counted
          mixed(20.5, starved=100.0), mixed(21.5, starved=80.0),
          decode(22.0, 21.6, starved=30.0, inflight=0),
          mixed(23.5, starved=500.0)]                  # after it
    got = reader("idle_unexplained_share").compute(sources(tl, capture(20.0, 23.0), trace))
    assert got == pytest.approx(100 * (1 - 0.21 / 0.3))


def test_a_record_the_captures_edge_cuts_counts_by_where_it_was_dispatched():
    trace = {"window_s": 3.0, "busy_s": 2.0}
    # a mixed step that began 40 ms before the capture and ended inside it: outside.
    straddles_in = mixed(19.96, starved=400.0)
    assert straddles_in["ts"] > T0 + 20.0 > straddles_in["start_ts"]
    # a decode chunk submitted inside the capture, fetched after it ended: inside.
    submitted_in = decode(23.05, 22.95, starved=250.0, inflight=0)
    # a decode chunk submitted before the capture, fetched inside: outside.
    submitted_before = decode(20.05, 19.95, starved=300.0, inflight=0)
    tl = [straddles_in, submitted_before, mixed(21.0, starved=250.0), submitted_in]
    got = reader("idle_unexplained_share").compute(sources(tl, capture(20.0, 23.0), trace))
    assert got == pytest.approx(100 * (1 - 0.5 / 1.0))
    # spans that claim more than the device idled read 0, not a negative share
    tl.append(mixed(22.0, starved=900.0))
    assert reader("idle_unexplained_share").compute(sources(tl, capture(20.0, 23.0), trace)) == 0.0
    # without capture_epoch_ns the reply's outer stamps place it
    loose = {"jax_trace": {"start_epoch_ns": int((T0 + 20.0) * 1e9), "stop_epoch_ns": int((T0 + 23.0) * 1e9)}}
    assert reader("idle_unexplained_share").compute(sources(tl[:4], loose, trace)) == pytest.approx(50.0)


def test_sidecar_http_ms_adds_the_two_edges_of_each_answered_request():
    reqs = [SimpleNamespace(trace_id=f"t{i}", error=None) for i in range(3)] + [
        SimpleNamespace(trace_id="t9", error="refused")]
    access = {"t0": {"recv_to_submit_ms": 2.0, "first_token_to_write_ms": 0.5, "queue_wait_ms": 1.0},
              "t1": {"recv_to_submit_ms": 3.0, "first_token_to_write_ms": 1.0},
              "t2": {"recv_to_submit_ms": 9.0, "first_token_to_write_ms": None},   # never wrote a frame
              "t9": {"recv_to_submit_ms": 50.0, "first_token_to_write_ms": 50.0}}
    assert reader("sidecar_http_ms").compute(sources([], access=access, requests=reqs)) == pytest.approx(3.25)


def test_start_up_readers_take_the_stages_from_the_status_document():
    status = {"startup": {"import_s": 3.4, "backend_s": 2.0, "params_s": 6.5, "quantize_s": 0.0, "kv_pool_s": 0.4,
                          "structured_s": 0.2, "observatory_s": 0.01, "warmup_s": 4.1, "listen_s": 0.05,
                          "total_s": 17.2}}
    assert reader("start_import_s").compute(sources([], status)) == 3.4
    assert reader("start_engine_build_s").compute(sources([], status)) == pytest.approx(7.1)
    status["startup"].pop("kv_pool_s")
    assert reader("start_engine_build_s").compute(sources([], status)) is None


NEW = ("mixed_step_host_ms", "mixed_step_host_ms.batch", "host_starved_share", "idle_unexplained_share",
       "sidecar_http_ms", "start_import_s", "start_engine_build_s")


@pytest.mark.parametrize("name", NEW)
def test_a_parent_commit_document_reads_as_nothing(name):
    """The parent's timeline has no phases, start_ts or starved_ms, its status
    no startup or jax_trace, its access log neither new field: each reader says
    None and raises nothing, so the line leaves the metric out."""
    reqs = [SimpleNamespace(trace_id="t0", error=None)]
    doc = sources([parent_record("mixed", 1.0), parent_record("decode", 1.2)],
                  status={"accelerator": {"platform": "tpu"}, "timeline": {"records": 2}},
                  trace={"window_s": 3.0, "busy_s": 2.7, "class_s": {}},
                  access={"t0": {"queue_wait_ms": 1.0, "prefill_ms": 140.0, "decode_ms": 900.0}},
                  requests=reqs)
    assert reader(name).compute(doc) is None


@pytest.mark.parametrize("name", NEW)
def test_every_new_entry_is_appended_with_its_readers_unit_and_layer(name):
    import json
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    entry = bench["per_layer"][names.index(name)]
    mod = reader(name)
    assert entry["unit"] == mod.UNIT and entry["layer"] == mod.LAYER
    assert entry["source"] in ("program_span", "program_counter")
    if (BENCH / "layers" / f"{name}.py").is_file():
        assert entry["moves"] == mod.MOVES
    assert "jax" not in mod.__dict__  # readers stay stdlib: the parent never holds the chip
