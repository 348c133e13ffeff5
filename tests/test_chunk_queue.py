"""How many decode chunks the scheduler leaves queued behind the running one
(ISSUE 48), on the CPU with the tiny preset, the loop stepped by hand on the
test's thread.

What is held here, at ``pipeline_depth`` 1, 2 and the default (which is 1):
in steady chained decode a pass submits chunk N+1 BEFORE it fetches chunk N
and leaves exactly ``depth`` chunks unfetched, so ``depth + 1`` is the most
ever in flight (``chunks_in_flight_max``), with a slot free or with none; a
request that arrives while the thread is inside a fetch is admitted at the
next pass, having waited out the chunk being fetched and the ``depth`` behind
it (at most 16 decode steps at depth 1), and its first mixed record says what
the admission drained (``prep.drained_steps``); a bucketed admission
(``mixed_step=False``) drains nothing and still gets a chunk dispatched behind
its prefill in the same pass; the chained horizon reserves pages for
``depth + 1`` chunks. No number here is a speed.
"""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from inference_gateway_tpu.otel.profiling import StepTimeline
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler, _Inflight

DEPTHS = [None, 1, 2]  # None: whatever EngineConfig defaults to
HACK = Path(__file__).resolve().parent.parent / "hack"


def _hack(name: str):
    spec = importlib.util.spec_from_file_location(name, HACK / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _depth_of(depth: "int | None") -> int:
    return 1 if depth is None else depth


def _engine(depth: "int | None", **over) -> Engine:
    kw = dict(model="test-tiny", attention="paged", page_size=16, max_slots=4, max_seq_len=256,
              dtype="float32", use_mesh=False, mixed_step=True, prefix_cache=False)
    if depth is not None:
        kw["pipeline_depth"] = depth
    kw.update(over)
    return Engine(EngineConfig(**kw))


def _request(i: int, prompt_len: int, seen: list, **kw) -> GenRequest:
    rng = np.random.default_rng(4800 + i)
    return GenRequest(prompt_ids=[int(t) for t in rng.integers(1, 250, prompt_len)],
                      callback=lambda *a: seen.append(a), temperature=0.7, seed=7 + i, **kw)


def _spy_chunks(engine: Engine) -> list:
    """Every decode chunk's ("submit", n) and ("fetch", n) in the order the
    scheduler thread made them, n the chunk's ordinal by dispatch."""
    log: list = []
    handles: list = []  # kept, so that identity tells the chunks apart
    submit, fetch = engine.decode_chunk_submit, engine.decode_chunk_fetch

    def _submit(*a, **kw):
        handles.append(submit(*a, **kw))
        log.append(("submit", len(handles) - 1))
        return handles[-1]

    def _fetch(handle):
        log.append(("fetch", next(n for n, h in enumerate(handles) if h is handle)))
        return fetch(handle)

    engine.decode_chunk_submit, engine.decode_chunk_fetch = _submit, _fetch
    return log


def _unfetched(sched: Scheduler) -> int:
    return sum(isinstance(h, _Inflight) for h in sched._handles)


def _steady(depth: "int | None"):
    """One long request decoding in chained chunks, the queue at its steady
    length. Returns (scheduler, engine, the chunk log, its callback's list)."""
    engine = _engine(depth)
    sched = Scheduler(engine)
    sched.timeline = StepTimeline(4096)
    log = _spy_chunks(engine)
    seen: list = []
    sched.submit(_request(0, 12, seen, max_tokens=200))
    for _ in range(3 + _depth_of(depth)):
        sched._pass()
    assert engine._dev_carry is not None and _unfetched(sched) == _depth_of(depth)
    return sched, engine, log, seen


def test_the_default_depth_is_one():
    assert EngineConfig().pipeline_depth == 1


@pytest.mark.parametrize("depth", DEPTHS)
def test_steady_decode_leaves_depth_chunks_unfetched_and_submits_before_it_fetches(depth):
    d = _depth_of(depth)
    sched, engine, log, _ = _steady(depth)
    assert engine.config.pipeline_depth == d
    for _ in range(6):
        before = len(log)
        sched._pass()
        # A steady pass: one chained submit, THEN one fetch, of the chunk
        # dispatched ``depth`` before it; ``depth`` stay unfetched.
        (op_s, n_s), (op_f, n_f) = log[before:]
        assert (op_s, op_f) == ("submit", "fetch") and n_s - n_f == d
        assert _unfetched(sched) == d
    at = {entry: i for i, entry in enumerate(log)}
    fetched = [n for op, n in log if op == "fetch"]
    assert fetched == sorted(fetched) and len(fetched) >= 6
    for n in fetched:
        assert at[("submit", n + 1)] < at[("fetch", n)]  # the device never waits for the host
    assert sched.chunks_in_flight_max == d + 1


@pytest.mark.parametrize("depth", DEPTHS)
def test_the_queue_is_as_long_with_every_slot_taken_and_after_a_row_ends(depth):
    """One count for every state of the slots: a closed loop that queues
    for slots runs the queue an open loop runs."""
    d = _depth_of(depth)
    engine = _engine(depth)
    sched = Scheduler(engine)
    log = _spy_chunks(engine)
    seen: list = [[] for _ in range(4)]
    for i in range(4):
        sched.submit(_request(i, 10 + i, seen[i], max_tokens=60 if i == 3 else 200))
    for _ in range(40):
        sched._pass()
        if not sched._free and not sched._waiting and _unfetched(sched) == d:
            break
    assert not sched._free and len(sched._slots) == 4

    def steady_pass():
        before = len(log)
        sched._pass()
        (op_s, n_s), (op_f, n_f) = log[before:]
        assert (op_s, op_f) == ("submit", "fetch") and n_s - n_f == d
        assert _unfetched(sched) == d

    for _ in range(3):
        steady_pass()
    for _ in range(40):
        if sched._free:
            break
        sched._pass()
    assert sched._free and [fin for *_, fin, _ in seen[3]][-1]
    for _ in range(3):
        steady_pass()
    assert sched.chunks_in_flight_max == d + 1


@pytest.mark.parametrize("depth", DEPTHS)
def test_a_bucketed_admission_gets_a_chunk_behind_its_prefill_in_the_same_pass(depth):
    """Without the mixed step (tp / pp engines, SERVING_MIXED_STEP_ENABLE=false)
    an admission drains nothing: its prefill scatters into the device carry
    behind the queued chunks, and the pass that admits dispatches a chained
    chunk behind the prefill and leaves it unfetched, so the device does not
    run dry while the host processes the prefill."""
    d = _depth_of(depth)
    engine = _engine(depth, mixed_step=False)
    assert not engine.mixed_ok
    sched = Scheduler(engine)
    log = _spy_chunks(engine)
    seen_a: list = []
    seen_b: list = []
    sched.submit(_request(0, 12, seen_a, max_tokens=200))
    for _ in range(3 + d):
        sched._pass()
    assert engine._dev_carry is not None and _unfetched(sched) == d
    fetched_before = [n for op, n in log if op == "fetch"]
    sched.submit(_request(1, 20, seen_b, max_tokens=24))
    before = len(log)
    sched._pass()                     # admits: prefill behind the queued chunks, a chunk behind it
    ops = log[before:]
    assert ops[0][0] == "submit", ops  # dispatched before anything is fetched
    newest = ops[0][1]
    assert [op for op, _ in ops[1:]] == ["fetch"] * d  # the chunks older than the prefill, no more
    assert ("fetch", newest) not in ops
    assert list(sched._handles) and all(isinstance(h, _Inflight) for h in sched._handles)
    assert _unfetched(sched) == 1     # the one behind the prefill; the prefill itself is resolved
    assert seen_b, "the prefill's first token is out before its decode continuation"
    assert len([n for op, n in log if op == "fetch"]) == len(fetched_before) + d
    for _ in range(3):                # and the queue is back at its steady length
        sched._pass()
    assert _unfetched(sched) == d
    assert sched.chunks_in_flight_max == d + 1


@pytest.mark.parametrize("depth", DEPTHS)
def test_an_arrival_inside_a_fetch_waits_out_depth_plus_one_chunks_and_its_record_says_so(depth):
    d = _depth_of(depth)
    sched, engine, log, _ = _steady(depth)
    chunk = engine.config.decode_chunk
    arrived: dict = {}
    seen_b: list = []
    fetch = engine.decode_chunk_fetch

    def _fetch(handle):
        if not arrived:
            # The thread is "blocked" here: the device holds this chunk and
            # whatever is queued behind it, none of it cancellable.
            arrived["unfetched_steps"] = handle.n_steps + sum(
                h.n_steps for h in sched._handles if isinstance(h, _Inflight))
            arrived["fetches"] = sum(op == "fetch" for op, _ in log) + 1  # with this one
            sched.submit(_request(1, 20, seen_b, max_tokens=4))
        return fetch(handle)

    engine.decode_chunk_fetch = _fetch
    sched._pass()                     # the arrival falls inside this pass's fetch
    assert arrived["unfetched_steps"] == (d + 1) * chunk
    if d == 1:
        assert arrived["unfetched_steps"] <= 16
    assert not seen_b and _unfetched(sched) == d
    sched._pass()                     # the very next pass admits: it drains what is queued, no more
    drained = sum(op == "fetch" for op, _ in log) - arrived["fetches"]
    assert drained == d
    for _ in range(40):
        if seen_b:
            break
        sched._pass()
    assert seen_b, "the arrival was never served"
    opened = [t for t in sched.timeline.tail() if t["kind"] == "mixed" and "prep" in t]
    assert opened[-1]["prep"]["drained_steps"] == d * chunk
    assert set(opened[-1]["prep"]) == {"ms", "register_ms", "drain_ms", "drained_steps"}
    # The first admission found nothing queued.
    assert opened[0]["prep"]["drained_steps"] == 0
    assert sched.chunks_in_flight_max == d + 1
    # What hack/timeline_run.py keeps of such a run, as hack/train_split.py prints it.
    said = _hack("train_split").split({"entries": sched.timeline.tail(),
                                       "scheduler": {"chunks_in_flight_max": sched.chunks_in_flight_max}})
    assert said["drained_steps"]["max"] == d * chunk and said["drained_steps"]["admissions"] == len(opened)
    assert said["drained_steps"]["count_by_steps"] == {"0": 1, str(d * chunk): 1}
    assert said["chunks_in_flight_max"] == d + 1


@pytest.mark.parametrize("depth", DEPTHS)
def test_the_chained_horizon_reserves_pages_for_depth_plus_one_chunks(depth):
    d = _depth_of(depth)
    engine = _engine(depth)
    chunk, ps = engine.config.decode_chunk, engine.config.page_size
    engine._pred_pos[:] = 0
    engine._pred_pos[1] = 21
    need = np.zeros((engine.config.max_slots,), bool)
    need[1] = True
    engine._reserve_horizon_pages(need, chunk)
    want = 21 + (d + 1) * chunk
    pages = -(-want // ps)
    assert int(engine._reserved[1]) == pages * ps
    assert len(engine.allocator.pages_of(1)) == pages
    assert int(engine._reserved[0]) == 0 and not engine.allocator.pages_of(0)


async def test_status_carries_the_most_chunks_in_flight():
    from inference_gateway_tpu.netio.server import Headers, Request
    from inference_gateway_tpu.serving.server import SidecarServer

    sched, engine, _, _ = _steady(None)
    sidecar = SidecarServer(engine, scheduler=sched, served_model_name="test-tiny")
    resp = await sidecar.debug_status(Request(method="GET", path="/debug/status", query={},
                                              headers=Headers(), body=b""))
    status = json.loads(resp.body)
    assert status["scheduler"]["chunks_in_flight_max"] == 2
    assert status["decode"] == {"chunk": 8, "pipeline_depth": 1}


@pytest.mark.parametrize("chunk_steps", [8, 2])
def test_the_queue_model_drains_depth_chunks_at_most_and_a_chunk_less_is_a_shorter_wait(chunk_steps):
    """hack/admission_wait_model.py walks the same loop: what an admission
    drains is bounded as the scheduler's is, and the second queued chunk is
    about a chunk of every part of a first token's wait."""
    model = _hack("admission_wait_model")
    kw = dict(chunk_ms=89.3, mixed_ms=50.2, prompt_steps=1, rate=4.2, chunk_steps=chunk_steps, seconds=120.0)
    two, one = model.simulate(depth=2, **kw), model.simulate(depth=1, **kw)
    assert two["drained_steps_max"] == 2 * chunk_steps and one["drained_steps_max"] == chunk_steps
    assert two["requests"] == one["requests"] > 400
    chunk = 89.3 * chunk_steps / 8
    assert 0.4 * chunk < two["ttft_p50"] - one["ttft_p50"] < 1.2 * chunk, (two, one)
    assert two["queue_wait_p95"] > one["queue_wait_p95"] and two["prefill_p50"] >= one["prefill_p50"]
    assert one["ttft_p50"] > 50.2  # never under the one mixed step
