"""The idle ledger's five readers (ISSUE 38) on hand-made sources: a window of
timeline records that carry ``starved_by``, the same window as the parent
commit writes it (no ``starved_by``), and a hand-made capture: a device line of
operations and programs and the scheduler thread's annotations, with a gap
under an annotation, a gap under none, a fetch that outlasts its program by
1.4 ms, and the same device moved 2.5 ms early on its own clock. The entries of
``BENCHMARK.json`` are found by name, wherever they stand."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import idle_ledger  # noqa: E402

NAMES = ("fetch_return_ms", "idle_unspanned_share", "starved_slot_share", "starved_delivery_share",
         "starved_unspanned_share")
SPEC = json.loads((BENCH / "trace_classes.json").read_text())
T0 = 1_790_000_000.0


def reader(name):
    import run
    return run.load_reader("layers", name)


def record(kind, starved_ms, by=None, at=1.0):
    rec = {"kind": kind, "start_ts": T0 + at, "ts": T0 + at + 0.05, "duration_ms": 50.0, "steps": 1, "batch": 9,
           "tokens": 9, "starved_ms": starved_ms, "inflight_at_dispatch": 0, "context_tokens": 4000,
           "phases": {"build": 0.3, "fetch_wait": 48.0, "fetch_copy": 0.2, "emit": 1.0}}
    if by is not None:
        rec["starved_by"] = by
    return rec


WINDOW = [
    record("mixed", 5.0, {"emit": 0.6, "release": 0.3, "release_slot": 0.2, "rows": 0.4, "build": 0.3, "pages": 0.2,
                          "assemble": 0.1, "upload_dispatch": 1.4, "record": 0.2, "loop": 0.3, "register_slot": 1.0}),
    record("mixed", 3.0, {"wake": 1.5, "rows": 0.5, "build": 0.2, "upload_dispatch": 0.8}, at=2.0),
    record("decode", 4.0, {"emit": 1.0, "deliver": 0.5, "build": 0.6, "upload_dispatch": 1.7, "loop": 0.2}, at=3.0),
    record("decode", 0.0, at=4.0),  # a chained submit: not starved, no ledger
]


def ms(a, b):
    """An event from ``a`` to ``b`` milliseconds after the capture's second 1."""
    return 1.0 + a / 1e3, (b - a) / 1e3


def event(name, a, b):
    start, dur = ms(a, b)
    return (name, start, dur)


# The device: a mixed step, 4.5 ms of nothing, a mixed step, 10 ms of nothing,
# then two decode chunks back to back (the second chained behind the first).
OPS = [event("%fusion.1", 0, 20), event("%fusion.2", 20.01, 50), event("%fusion.3", 54.5, 100),
       event("%fusion.4", 110, 200), event("%fusion.5", 200, 300)]
MODULES = [event("jit__mixed_step_fn(1)", 0, 50), event("jit__mixed_step_fn(1)", 54.5, 100),
           event("jit__decode_chunk_fn_paged_ee(2)", 110, 200), event("jit__decode_chunk_fn_paged_ee(2)", 200, 300)]
ANNOTATIONS = [
    event("sched.loop", -100, 103), event("sched.admit.rows", -10, 102),
    event("engine.mixed.upload_dispatch", -5, 0), event("sched.deliver", 1, 3),
    event("engine.mixed.fetch", 3, 51), event("engine.mixed.fetch_copy", 51, 51.4),       # 1.4 ms after the program
    event("sched.emit", 51.6, 52.6), event("sched.release", 52.0, 52.4), event("structured.release_slot", 52.1, 52.3),
    event("sched.mixed.build", 52.7, 53.0), event("engine.mixed.upload_dispatch", 53.1, 54.5),
    event("engine.mixed.fetch", 56, 101.2), event("engine.mixed.fetch_copy", 101.2, 101.4),
    # from 103 to 110 the thread is under no annotation at all
    event("engine.chunk.fetch", 150, 200.8), event("engine.chunk.fetch_copy", 200.8, 201.0),  # a chunk is behind it
    event("engine.chunk.fetch", 250, 300.9), event("engine.chunk.fetch_copy", 300.9, 301.1),
]


def sources(timeline, events=True):
    trace = {"window_s": 0.3, "busy_s": 0.2855}
    if events:
        trace["idle_ledger_events"] = {"ops": OPS, "modules": MODULES, "annotations": ANNOTATIONS, "spec": SPEC}
    return {"timeline": timeline, "trace": trace, "status": {}, "window": {"seconds": 50.0, "epoch_open": T0}}


@pytest.mark.parametrize("name", NAMES)
def test_every_entry_is_in_the_benchmark_with_its_readers_unit_layer_and_every_cell(name):
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = reader(name)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (entry["unit"], entry["layer"], entry["moves"])
    assert entry["better"] == "lower" and entry["moves"] == "tokens_per_s"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]][:len(entry["workloads"])]
    assert len(entry["workloads"]) >= 7
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] not in NAMES}


def test_the_three_shares_sum_their_causes_over_every_record_of_the_window():
    src = sources(WINDOW)
    assert reader("starved_slot_share").compute(src) == pytest.approx(100 * (1.0 + 0.2 + 0.3) / 1e3 / 50.0)
    assert reader("starved_delivery_share").compute(src) == pytest.approx(100 * (0.6 + 1.0 + 0.5) / 1e3 / 50.0)
    assert reader("starved_unspanned_share").compute(src) == pytest.approx(100 * (0.3 + 0.2 + 1.5 + 0.2) / 1e3 / 50.0)
    # together they stay under the whole: the submit's own phases are the rest
    whole = reader("host_starved_share").compute(src)
    parts = sum(reader(n).compute(src) for n in NAMES[2:])
    assert whole == pytest.approx(100 * 12.0 / 1e3 / 50.0) and parts < whole


@pytest.mark.parametrize("name", NAMES)
def test_every_reader_says_nothing_on_records_without_starved_by(name):
    parent = [{k: v for k, v in rec.items() if k != "starved_by"} for rec in WINDOW]
    assert reader(name).compute(sources(parent)) is None
    assert reader(name).compute(sources([])) is None


def test_innermost_cuts_a_threads_line_at_every_edge_of_its_nested_annotations():
    got = idle_ledger.innermost([event("sched.emit", 0, 10), event("sched.release", 2, 6),
                                 event("structured.release_slot", 3, 4), event("sched.deliver", 12, 13)])
    want = [(0, 2, "sched.emit"), (2, 3, "sched.release"), (3, 4, "structured.release_slot"),
            (4, 6, "sched.release"), (6, 10, "sched.emit"), (12, 13, "sched.deliver")]
    assert [(round((a - 1) * 1e3, 6), round((b - 1) * 1e3, 6), n) for a, b, n in got] == want


def test_idle_seconds_go_to_the_innermost_annotation_and_to_loop_where_there_is_none():
    got = idle_ledger.account(OPS, MODULES, ANNOTATIONS, SPEC)
    assert got["idle_s"] == pytest.approx(14.5e-3) and got["between_operations_s"] == pytest.approx(1e-5)
    by = {k: round(v * 1e3, 6) for k, v in got["idle_by_span"].items()}
    # the first gap, 50 -> 54.5 ms, under the annotations that were open in it
    # (fetch 1.0, fetch_copy 0.4, rows 0.2 + 0.1 + 0.1, emit 0.4 + 0.2, release 0.1 + 0.1,
    # release_slot 0.2, build 0.3, upload_dispatch 1.4), and the second, 100 -> 110 ms:
    # fetch 1.2, fetch_copy 0.2, rows 0.6, sched.loop alone 1.0, no annotation at all 7.0
    assert by == {"loop": 8.0, "fetch": 2.2, "upload_dispatch": 1.4, "rows": 1.0, "emit": 0.6, "fetch_copy": 0.6,
                  "build": 0.3, "release": 0.2, "release_slot": 0.2}
    assert sum(by.values()) == pytest.approx(14.5)
    assert got["unspanned_s"] == pytest.approx(8e-3)
    assert got["idle_by_annotation"]["engine.mixed.fetch"] == pytest.approx(2.2e-3)


def test_the_way_back_is_the_programs_end_to_the_copys_end_on_host_stepped_steps_alone():
    got = idle_ledger.fetch_returns(ANNOTATIONS, MODULES, idle_ledger._step_pattern(SPEC))
    # both mixed steps and the last chunk; the chunk with another queued behind it is left out
    assert [round(r["return_ms"], 6) for r in got] == [1.4, 1.4, 1.1]
    assert [round(r["wake_ms"], 6) for r in got] == [1.0, 1.2, 0.9]
    assert [round(r["copy_ms"], 6) for r in got] == [0.4, 0.2, 0.2]
    assert reader("fetch_return_ms").compute(sources(WINDOW)) == pytest.approx(1.4)


def test_a_device_plane_that_reads_early_is_put_on_the_hosts_clock_by_causality():
    """The chip's captures read the device plane 0.3-2.9 ms early. Moved 2.5 ms
    early, the hand-made device says its programs began before the calls that
    dispatched them; the account finds the shift between its two hard bounds
    (a program starts no earlier than its call began: 1.1 ms; a fetch returns
    no earlier than its program ends: 0.8 + 2.5 ms) and reads as before."""
    early = [[(n, s - 2.5e-3, d) for n, s, d in evs] for evs in (OPS, MODULES)]
    got, straight = idle_ledger.account(*early, ANNOTATIONS, SPEC), idle_ledger.account(OPS, MODULES, ANNOTATIONS, SPEC)
    assert got["device_clock_shift_ms"] == pytest.approx(2.5) and got["device_clock_anchors"] == 2
    assert got["device_clock_shift_bounds_ms"] == pytest.approx([1.1, 3.3])
    assert straight["device_clock_shift_ms"] == pytest.approx(0.0, abs=1e-9)
    assert got["idle_by_span"] == pytest.approx(straight["idle_by_span"])
    assert got["fetch_return_ms"] == pytest.approx(1.4) and got["host_stepped_steps"] == 3
    # as the file stands, the first 2.5 ms of every gap fall where the host still waited
    assert got["idle_by_span_unshifted"]["fetch"] > straight["idle_by_span"]["fetch"] + 4e-3
    assert straight["idle_by_span_unshifted"] == pytest.approx(straight["idle_by_span"])


def test_idle_unspanned_share_is_measured_on_the_capture_and_needs_the_ledger_on_the_records():
    assert reader("idle_unspanned_share").compute(sources(WINDOW)) == pytest.approx(100 * 8.0 / 14.5)
    assert reader("idle_unspanned_share").compute(sources(WINDOW, events=False)) is None  # no file, no events


def test_the_by_cause_table_of_a_saved_timeline_counts_each_kind_of_starved_dispatch():
    got = idle_ledger.table(WINDOW)
    assert got["mixed"]["records"] == 2 and got["decode"]["records"] == 1
    assert got["mixed"]["starved_ms_total"] == pytest.approx(8.0)
    assert got["mixed"]["by_cause_total_ms"]["upload_dispatch"] == pytest.approx(2.2)
    assert got["mixed"]["by_cause_median_ms"]["wake"] == pytest.approx(0.75)
