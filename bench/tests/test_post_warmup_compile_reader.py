"""``post_warmup_compile_s`` (ISSUE 49) on hand-made status documents: events sum
to seconds, a ledger with no event reads 0.0 and not nothing, a status without
a ledger reads nothing. Its entry of ``BENCHMARK.json`` is found by name,
wherever it stands."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

NAME = "post_warmup_compile_s"


def reader():
    import run
    return run.load_reader("layers", NAME)


def status(events):
    return {"status": {"device": {"compile": {"compiles": 9 + len(events), "recompiles": len(events), "warmed": True,
                                              "recompile_events": events}}}}


# The parent's first biased request, as its sidecar logged it (PERF.md section 6, PR 49).
PARENT = [{"program": "mixed_step_fn", "compile_ms": 2066.4, "diff": ["arg5: masked=False -> masked=True"]},
          {"program": "decode_chunk_fn_paged_ee", "compile_ms": 1864.8, "diff": ["arg14: masked=False -> masked=True"]}]


def test_the_events_sum_to_seconds():
    assert reader().compute(status(PARENT)) == pytest.approx(3.9312)


def test_a_ledger_with_no_event_reads_zero_and_not_nothing():
    got = reader().compute(status([]))
    assert got == 0.0 and got is not None and isinstance(got, float)


@pytest.mark.parametrize("sources", [{}, {"status": None}, {"status": {}}, {"status": {"device": {}}},
                                     {"status": {"device": {"compile": None}}},
                                     {"status": {"device": {"compile": {"compiles": 3}}}}],
                         ids=["no_status", "status_none", "no_device", "no_ledger", "ledger_none", "no_events_key"])
def test_a_status_without_a_ledger_reads_nothing(sources):
    assert reader().compute(sources) is None


def test_the_entry_is_the_readers_and_lists_every_cell():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    mod = reader()
    assert (entry["unit"], entry["layer"], entry["moves"], entry["better"]) == (mod.UNIT, mod.LAYER, mod.MOVES, "lower")
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    # every cell reports the end-to-end metric it moves
    setup = next(m for m in bench["end_to_end"] if m["name"] == mod.MOVES)
    assert "workloads" not in setup
