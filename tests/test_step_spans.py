"""Step spans inside the scheduler and engine (ISSUE 24), on the CPU with the
tiny preset: what every timeline record carries (start on the span clock,
host phases, starvation), the request's two new phase stamps at the sidecar's
HTTP edges, the start-up spans, and the capture endpoint's new parameter.
No number here is a speed: the assertions are about order, sign and sums.
"""

import asyncio
import json
import socket
import time

import pytest

from inference_gateway_tpu.main import build_gateway
from inference_gateway_tpu.netio.client import HTTPClient, HTTPClientError
from inference_gateway_tpu.netio.server import Headers
from inference_gateway_tpu.otel.access_log import AccessLog
from inference_gateway_tpu.otel.profiling import StepTimeline, phase_span, span_clock_ns
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.server import SidecarServer, serve

TRACE_ID = "0af7651916cd43dd8448eb211c80319c"
TRACEPARENT = f"00-{TRACE_ID}-b7ad6b7169203331-01"
MIXED_PHASES = ("build", "lock_wait", "pages", "assemble", "upload_dispatch", "deliver",
                "fetch_wait", "fetch_copy", "emit")
STARTUP_KEYS = ("import_s", "params_s", "quantize_s", "kv_pool_s", "structured_s",
                "observatory_s", "warmup_s", "listen_s", "total_s")


def _engine_cfg() -> EngineConfig:
    return EngineConfig(model="test-tiny", attention="paged", page_size=16, max_slots=4,
                        max_seq_len=256, dtype="float32", use_mesh=False, mixed_step=True,
                        decode_chunk=4)


@pytest.fixture(scope="module")
def sidecar(aloop):
    engine = Engine(_engine_cfg())
    engine.warmup()
    access_log = AccessLog(service="tpu-sidecar", tail_size=64)
    server = SidecarServer(engine, served_model_name="test-tiny", access_log=access_log)
    port = aloop.run(server.start("127.0.0.1", 0))
    yield server, port, access_log
    aloop.run(server.shutdown())


async def _chat(port: int, max_tokens: int = 14, stream: bool = True, path: str = "",
                model: str = "test-tiny", content: str = "count to twenty, slowly") -> bytes:
    headers = Headers()
    headers.set("Content-Type", "application/json")
    headers.set("traceparent", TRACEPARENT)
    body = {"model": model, "stream": stream, "max_tokens": max_tokens,
            "messages": [{"role": "user", "content": content}],
            # ASCII only, whatever the seed's weights prefer: every token is a visible frame.
            "logit_bias": {str(i): 100 for i in range(65, 91)}}
    resp = await HTTPClient().post(f"http://127.0.0.1:{port}{path}/v1/chat/completions",
                                   json.dumps(body).encode(), headers=headers, stream=stream)
    assert resp.status == 200
    if not stream:
        return resp.body
    return b"".join([block async for block in resp.iter_raw()])


async def _records(server: SidecarServer, port: int, n_requests: int = 2) -> list[dict]:
    """The timeline records of ``n_requests`` chats sent one after another
    with an idle spell before each."""
    before = server.timeline.stats()["records"]
    for _ in range(n_requests):
        await asyncio.sleep(0.4)
        await _chat(port)
    await asyncio.sleep(0.1)  # tail chunks of the last stream
    return server.timeline.tail(server.timeline.stats()["records"] - before)


def _adds_up(pairs: list[tuple[float, float]]) -> bool:
    """(sum of phases, duration_ms) pairs: no sum passes its duration, and what a
    record leaves unexplained is under 10% of it. On the CPU the tiny model's
    records last a millisecond and share the GIL with the event loop, so the
    floor is 1 ms and one record in ten may miss."""
    assert all(total <= duration + 0.01 for total, duration in pairs), pairs
    near = [duration - total <= max(0.1 * duration, 1.0) for total, duration in pairs]
    return sum(near) >= 0.9 * len(near)


async def test_every_record_starts_before_it_ends_and_phases_are_not_negative(sidecar):
    server, port, _ = sidecar
    recs = [r for r in await _records(server, port) if r["kind"] in ("mixed", "decode")]
    assert {r["kind"] for r in recs} == {"mixed", "decode"}
    for r in recs:
        assert r["start_ts"] <= r["ts"]
        assert r["ts"] - r["start_ts"] == pytest.approx(r["duration_ms"] / 1e3, abs=2e-6)
        assert all(v >= 0 for v in r["phases"].values()), r
        assert r["starved_ms"] >= 0 and r["inflight_at_dispatch"] >= 0
        assert r["context_tokens"] > 0
        # What the benchmark's readers take from a record is where it was.
        assert {"kind", "duration_ms", "steps", "batch", "tokens", "kv_utilization"} <= set(r)


async def test_a_mixed_records_phases_add_up_to_its_duration(sidecar):
    server, port, _ = sidecar
    mixed = [r for r in await _records(server, port) if r["kind"] == "mixed"]
    assert mixed
    assert all(set(r["phases"]) == set(MIXED_PHASES) for r in mixed)
    # A step fetched with a program behind it (ISSUE 40: its ``submit_ts`` says
    # when it was dispatched) runs from its fetch, like a decode chunk.
    own = lambda r: ("fetch_wait", "fetch_copy", "emit") if "submit_ts" in r else MIXED_PHASES  # noqa: E731
    assert all(r["submit_ts"] <= r["start_ts"] for r in mixed if "submit_ts" in r)
    assert _adds_up([(sum(r["phases"][k] for k in own(r)), r["duration_ms"]) for r in mixed]), mixed
    # The admission's first step carries what came before it.
    assert all(set(r["prep"]) == {"ms", "register_ms", "drain_ms", "drained_steps"} for r in mixed if "prep" in r)
    first = next(r for r in mixed if "prep" in r)
    assert first["prep"]["ms"] >= first["prep"]["register_ms"] + first["prep"]["drain_ms"] > 0


async def test_a_decode_records_fetch_and_emit_add_up_and_its_submit_came_earlier(sidecar):
    server, port, _ = sidecar
    decode = [r for r in await _records(server, port) if r["kind"] == "decode"]
    assert decode
    for r in decode:
        assert {"build", "upload_dispatch", "fetch_wait", "fetch_copy", "emit"} <= set(r["phases"])
        assert r["submit_ts"] < r["start_ts"]
    # ISSUE 38: the fetch is two stamps, the wait and the copy.
    assert _adds_up([(r["phases"]["fetch_wait"] + r["phases"]["fetch_copy"] + r["phases"]["emit"],
                      r["duration_ms"]) for r in decode]), decode
    assert any(r["phases"]["build"] > 0 for r in decode)   # a fresh submit assembles
    assert any(r["phases"]["build"] == 0 for r in decode)  # a chained one does not


async def test_starvation_is_zero_on_a_chained_submit_and_positive_after_an_idle_spell(sidecar):
    server, port, _ = sidecar
    recs = await _records(server, port, n_requests=2)
    chained = [r for r in recs if r["kind"] == "decode" and r["inflight_at_dispatch"] > 0]
    assert chained and all(r["starved_ms"] == 0 for r in chained)
    firsts = [r for r in recs if r["kind"] == "mixed" and "prep" in r]
    assert len(firsts) == 2
    for r in firsts:
        assert r["inflight_at_dispatch"] == 0 and r["starved_ms"] > 0
    # The fresh decode submit behind a train reads its tokens on the device and
    # is dispatched with the train's last step unfetched (ISSUE 40): not starved.
    fresh = [r for r in recs if r["kind"] == "decode" and r["phases"]["build"] > 0]
    assert fresh and all(r["tokens_from"] == "device" and r["inflight_at_dispatch"] >= 1
                         and r["starved_ms"] == 0 for r in fresh)


async def test_a_host_stepped_record_says_how_many_arrays_its_submit_uploaded(sidecar):
    """ISSUE 25: `h2d_arrays` is 1 on every `mixed` record and on the
    fresh `decode` record (the packed buffer), 0 on a chained one."""
    server, port, _ = sidecar
    recs = await _records(server, port)
    mixed = [r for r in recs if r["kind"] == "mixed"]
    fresh = [r for r in recs if r["kind"] == "decode" and r["phases"]["build"] > 0]
    chained = [r for r in recs if r["kind"] == "decode" and r["phases"]["build"] == 0]
    assert mixed and fresh and chained
    assert [r["h2d_arrays"] for r in mixed] == [1] * len(mixed)
    assert [r["h2d_arrays"] for r in fresh] == [1] * len(fresh)
    assert [r["h2d_arrays"] for r in chained] == [0] * len(chained)


async def test_mixed_records_count_the_tokens_whose_rows_take_the_wide_tile(sidecar):
    """ISSUE 34: /debug/status names the ragged kernel's tiling for the
    engine's layout, and every `mixed` record says how many of its tokens
    sat in rows of `wide_from` queries or more: the admission's prompt
    chunk (what the prefix cache left of a prompt far longer than that, so
    all but whole pages of it), never a decode row's one."""
    from inference_gateway_tpu.ops.paged_attention import ragged_tile_plan

    server, port, _ = sidecar
    cfg, engine = server.engine.model_cfg, server.engine
    plan = ragged_tile_plan(cfg.num_heads, cfg.num_kv_heads, cfg.hd, 16, engine.page_cfg.max_pages_per_slot,
                            engine.dtype)
    status = (await HTTPClient().get(f"http://127.0.0.1:{port}/debug/status")).json()
    assert status["ragged_attention"] == plan == engine.ragged_attention_plan
    # ISSUE 44: one width, the pages a visit of a wide tile and of a one-query
    # row, and which kernel the one-query rows take.
    assert set(plan) == {"wide_tile", "wide_from", "rows_per_pass", "wide_pages_per_visit", "unmasked_visits",
                         "one_query_rows", "one_query_pages_per_visit"}
    assert plan["one_query_rows"] == "decode_kernel"
    assert plan["one_query_pages_per_visit"] == status["decode_attention"]["pages_per_visit"]
    assert 1 <= plan["wide_pages_per_visit"] <= engine.page_cfg.max_pages_per_slot
    # ISSUE 37: and the decode kernel's page walk for the same layout.
    walk = status["decode_attention"]
    assert walk == server.engine.decode_attention_plan
    assert walk["skips_empty_slots"] and 1 <= walk["pages_per_visit"] <= server.engine.page_cfg.max_pages_per_slot
    assert walk["operand_dtype"] in ("float32", "bfloat16")
    before = server.timeline.stats()["records"]
    for question in ("what do the tiles of a ragged kernel have to do with a mixed step",
                     "and why would a row of many queries want another tile than a row of one"):
        await asyncio.sleep(0.4)
        await _chat(port, content=question)
    await asyncio.sleep(0.1)
    recs = server.timeline.tail(server.timeline.stats()["records"] - before)
    mixed = [r for r in recs if r["kind"] == "mixed"]
    assert len(mixed) == 2
    for name in ("attn_wide_tokens", "attn_pages_walked", "attn_pages_masked"):
        assert all(name not in r for r in recs if r["kind"] != "mixed")
    for r in mixed:
        assert r["batch"] == 1  # idle before: the prompt's row and no decode row
        adopted = r["context_tokens"] - r["attn_wide_tokens"]
        assert r["attn_wide_tokens"] >= plan["wide_from"] and adopted >= 0 and adopted % 16 == 0
        # ISSUE 44: the pages the chunk's tiles walked in every layer (each
        # tile at least the page its queries sit on), and of them the pages
        # of visits that took the mask (its diagonal, at the least).
        tiles = -(-r["attn_wide_tokens"] // plan["wide_tile"])
        assert cfg.num_layers * tiles <= r["attn_pages_masked"] <= r["attn_pages_walked"]
        assert r["attn_pages_walked"] <= cfg.num_layers * tiles * -(-r["context_tokens"] // 16)


async def test_records_and_status_count_what_was_delivered_behind_a_dispatch(sidecar):
    """ISSUE 29: every `mixed` record and the fresh `decode` record carry
    `deferred_flushes` and a `deliver` phase, a chained one neither, and
    /debug/status keeps the running totals. A second stream is admitted
    while the first decodes, so some flushes did wait."""
    server, port, _ = sidecar
    client = HTTPClient()
    status = (await client.get(f"http://127.0.0.1:{port}/debug/status")).json()
    totals = status["scheduler"]
    assert all(type(totals[k]) is int for k in
               ("steps_completed", "flushes_deferred", "flushes_immediate"))
    before = server.timeline.stats()["records"]
    first = asyncio.ensure_future(_chat(port, max_tokens=200))
    while not server.scheduler._slots:
        await asyncio.sleep(0.001)
    await asyncio.gather(first, _chat(port, max_tokens=30))
    await asyncio.sleep(0.1)
    recs = server.timeline.tail(server.timeline.stats()["records"] - before)
    mixed = [r for r in recs if r["kind"] == "mixed"]
    fresh = [r for r in recs if r["kind"] == "decode" and r["phases"]["build"] > 0]
    chained = [r for r in recs if r["kind"] == "decode" and r["phases"]["build"] == 0]
    assert len(mixed) >= 2 and fresh and chained
    for r in mixed + fresh:
        assert type(r["deferred_flushes"]) is int and 0 <= r["deferred_flushes"] <= r["batch"]
        assert type(r["phases"]["deliver"]) is float and r["phases"]["deliver"] >= 0
    assert all("deferred_flushes" not in r and "deliver" not in r["phases"] for r in chained)
    hidden = sum(r["deferred_flushes"] for r in mixed + fresh)
    assert hidden >= 1  # the first stream's rows, behind the second's admission
    after = (await client.get(f"http://127.0.0.1:{port}/debug/status")).json()["scheduler"]
    assert after["flushes_deferred"] - totals["flushes_deferred"] == hidden
    assert after["flushes_immediate"] > totals["flushes_immediate"]
    assert after["steps_completed"] > totals["steps_completed"]
    assert not server.scheduler._deferred


async def test_an_idle_scheduler_accrues_no_starvation(sidecar):
    server, port, _ = sidecar
    await _chat(port, max_tokens=4)
    await asyncio.sleep(0.1)
    before = server.timeline.stats()["records"]
    await asyncio.sleep(1.0)  # nothing to do: not starvation, and no host gap
    await _chat(port, max_tokens=4)
    await asyncio.sleep(0.1)
    recs = server.timeline.tail(server.timeline.stats()["records"] - before)
    first = recs[0]
    assert first["kind"] == "mixed" and 0 < first["starved_ms"] < 500
    assert "host_gap_ms" not in first
    assert sum(r["starved_ms"] for r in recs) < 500


async def test_request_stamps_are_ordered_and_the_access_log_gains_two_fields(sidecar):
    server, port, access_log = sidecar
    seen = []
    submit = server.scheduler.submit
    server.scheduler.submit = lambda gen: (seen.append(gen), submit(gen))[1]
    try:
        raw = await _chat(port)
    finally:
        server.scheduler.submit = submit
    assert b"[DONE]" in raw
    ph = seen[0].phase_ns
    assert ph["recv"] <= ph["submit"] <= ph["admit"] <= ph["first_token"] <= ph["first_write"]
    for _ in range(200):
        line = next((e for e in reversed(access_log.tail) if e.get("trace_id") == TRACE_ID), None)
        if line is not None:
            break
        await asyncio.sleep(0.01)
    assert line["recv_to_submit_ms"] == round((ph["submit"] - ph["recv"]) / 1e6, 3)
    assert line["first_token_to_write_ms"] == round((ph["first_write"] - ph["first_token"]) / 1e6, 3)
    for key in ("queue_wait_ms", "prefill_ms", "decode_ms", "input_tokens", "output_tokens"):
        assert line[key] is not None  # nothing else on the line changed


async def test_a_detached_observer_stamps_nothing(sidecar, monkeypatch):
    """... and opens no annotation (ISSUE 38): with no observer the scheduler
    thread has no cause cursor bound, so every span on it is the shared no-op."""
    from inference_gateway_tpu.otel import profiling

    server, port, _ = sidecar
    sched, engine = server.scheduler, server.engine
    opened = []
    annotation = profiling.trace_annotation
    monkeypatch.setattr(profiling, "trace_annotation",
                        lambda name, **stats: (opened.append(name), annotation(name, **stats))[1])
    handles = []
    mixed, chunk = engine.mixed_step_submit, engine.decode_chunk_submit
    engine.mixed_step_submit = lambda *a, **k: (handles.append(mixed(*a, **k)), handles[-1])[1]
    engine.decode_chunk_submit = lambda *a, **k: (handles.append(chunk(*a, **k)), handles[-1])[1]
    timeline = sched.timeline
    await asyncio.sleep(0.3)  # let the loop go idle with the observers still on
    records = timeline.stats()["records"]  # the last stream's tail chunks are in by now
    sched.timeline = None
    sched._dev_touch = sched._starve_t0 = None
    await asyncio.sleep(0.3)  # the wait's next tick unbinds the cursor
    assert "sched.wake" in opened  # the ticks before the observers went
    del opened[:]
    try:
        await _chat(port)
        await asyncio.sleep(0.1)
        assert not sched._observing and not opened
        assert len(handles) >= 3 and all(h.phases is None for h in handles)
        assert sched._dev_touch is None and sched._starve_t0 is None
        assert timeline.stats()["records"] == records
    finally:
        engine.mixed_step_submit, engine.decode_chunk_submit = mixed, chunk
        sched.timeline = timeline


async def test_jax_trace_without_the_python_tracer_is_still_a_409_off_tpu(sidecar):
    server, port, _ = sidecar
    client = HTTPClient()
    for query in ("seconds=0.1&python_tracer=0", "seconds=0.1&python_tracer=1", "seconds=0.1"):
        resp = await client.get(f"http://127.0.0.1:{port}/debug/jax_trace?{query}")
        assert resp.status == 409
        assert resp.json() == {"captured": False, "reason": "device platform 'cpu' is not tpu"}
    status = (await client.get(f"http://127.0.0.1:{port}/debug/status")).json()
    assert "jax_trace" not in status  # nothing was captured


async def test_gateway_relays_an_inbound_traceparent_with_its_tracing_off(sidecar, aloop):
    server, port, access_log = sidecar
    gw = build_gateway(env={"TPU_API_URL": f"http://127.0.0.1:{port}/v1", "SERVER_PORT": "0",
                            "OLLAMA_API_URL": "http://127.0.0.1:1/v1",
                            "LLAMACPP_API_URL": "http://127.0.0.1:1/v1"})
    assert gw.otel is None or not gw.cfg.telemetry.tracing_enable
    gw_port = await gw.start("127.0.0.1", 0)
    try:
        before = len([e for e in access_log.tail if e.get("trace_id") == TRACE_ID])
        await _chat(gw_port, max_tokens=4, model="tpu/test-tiny")
        for _ in range(200):
            lines = [e for e in access_log.tail if e.get("trace_id") == TRACE_ID]
            if len(lines) > before:
                break
            await asyncio.sleep(0.01)
        assert len(lines) == before + 1, "the sidecar's line does not carry the client's trace id"
    finally:
        await gw.shutdown()


async def test_startup_spans_cover_the_start_and_fit_inside_the_time_to_healthy():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    t0 = time.perf_counter_ns()
    task = asyncio.ensure_future(serve(
        EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64, dtype="float32", use_mesh=False),
        host="127.0.0.1", port=port, started_ns=t0))
    client = HTTPClient()
    try:
        for _ in range(600):
            assert not task.done(), task.exception()
            try:
                if (await client.get(f"http://127.0.0.1:{port}/health")).status == 200:
                    break
            except HTTPClientError:
                pass  # not listening yet
            await asyncio.sleep(0.05)
        healthy_s = (time.perf_counter_ns() - t0) / 1e9
        startup = (await client.get(f"http://127.0.0.1:{port}/debug/status")).json()["startup"]
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
    assert set(STARTUP_KEYS) <= set(startup)
    assert all(v >= 0 for v in startup.values())
    stages = sum(v for k, v in startup.items() if k != "total_s")
    assert stages <= startup["total_s"] + 1e-3 <= healthy_s + 2e-3
    assert startup["params_s"] > 0 and startup["warmup_s"] > 0


def test_the_span_clock_is_epoch_time_that_never_runs_backwards():
    a = span_clock_ns()
    b = span_clock_ns()
    assert a <= b
    assert abs(a - time.time_ns()) < 5e9  # epoch, within NTP's reach of the anchor
    assert span_clock_ns(time.perf_counter_ns()) - span_clock_ns() < 1e8


def test_phase_span_adds_up_under_one_key_and_none_stamps_nothing():
    phases: dict = {}
    for _ in range(2):
        with phase_span(phases, "emit", "sched.emit"):
            time.sleep(0.002)
    assert list(phases) == ["emit"] and phases["emit"] >= 4.0
    with phase_span(None, "emit", "sched.emit") as nothing:
        assert nothing is None
    assert phase_span(None, "a", "b") is phase_span(None, "c", "d")  # one shared no-op


def test_a_bare_record_keeps_every_field_it_had_and_gains_its_start():
    timeline = StepTimeline(8)
    timeline.record("decode", 0.012, n_steps=8, batch=3, tokens=24, kv_utilization=0.25)
    timeline.record("mixed", 0.05, spans={"phases": {"build": 1.0}, "starved_ms": 2.5})
    plain, spanned = timeline.tail()
    assert {"ts", "kind", "duration_ms", "steps", "batch", "tokens", "kv_utilization",
            "queue_depth"} <= set(plain)
    assert plain["ts"] - plain["start_ts"] == pytest.approx(0.012, abs=1e-6)
    assert "phases" not in plain and "host_gap_ms" not in plain
    assert spanned["phases"] == {"build": 1.0} and spanned["starved_ms"] == 2.5


# ---------------------------------------------------------------------------
# What a record carries is a closed, documented list (ISSUE 46): stamps and
# counts, no estimate. docs/observability.md's table is held to the code.
# ---------------------------------------------------------------------------
COST_KEYS = {"flops", "hbm_bytes", "roofline_ms", "bound"}
KINDS = ("prefill", "decode", "mixed", "spec", "spec_ngram")


def _documented_keys() -> dict[str, tuple[set, set]]:
    """kind -> (keys of every record, keys where they apply), from the table
    under "Record keys by kind" in docs/observability.md."""
    import re
    from pathlib import Path

    text = (Path(__file__).resolve().parents[1] / "docs" / "observability.md").read_text()
    rows = text.split("#### Record keys by kind", 1)[1].split("\n\n| kind |", 1)[1].split("\n\n", 1)[0]
    table = {}
    for line in rows.splitlines()[2:]:
        name, always, sometimes = [cell.strip() for cell in line.strip().strip("|").split("|")]
        table[name.strip("`")] = (set(re.findall(r"`([a-z_0-9]+)`", always)),
                                  set(re.findall(r"`([a-z_0-9]+)`", sometimes)))
    return table


@pytest.fixture(scope="module")
def records_by_kind() -> dict[str, list[dict]]:
    """Timeline records of every kind, from four tiny engines driven by their
    schedulers: bucketed (prefill, decode), mixed, model-draft and n-gram
    speculation."""
    import threading

    import numpy as np

    from inference_gateway_tpu.serving.scheduler import Scheduler, generate_sync

    def run(**over) -> list[dict]:
        kw = dict(model="test-tiny", max_slots=4, max_seq_len=256, dtype="float32",
                  max_prefill_batch=2, use_mesh=False, decode_chunk=4)
        kw.update(over)
        sched = Scheduler(Engine(EngineConfig(**kw)))
        sched.timeline = StepTimeline(512)
        sched.start()
        try:
            rng = np.random.default_rng(0)
            prompts = [[int(t) for t in rng.integers(1, 250, n)] for n in (8, 40, 9)]
            threads = [threading.Thread(target=generate_sync, args=(sched, p), kwargs={"max_tokens": 12})
                       for p in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            generate_sync(sched, [7, 8, 9, 7, 8, 9, 7, 8], max_tokens=16)
        finally:
            sched.stop()
        return sched.timeline.tail(None)

    records = (run() + run(attention="paged", page_size=16, mixed_step=True, prefix_cache=False)
               + run(max_slots=2, spec_draft="test-tiny", spec_k=4)
               + run(max_slots=2, spec_draft="ngram", spec_k=4))
    return {kind: [r for r in records if r["kind"] == kind] for kind in KINDS}


def test_the_documented_kinds_are_the_schedulers():
    table = _documented_keys()
    assert set(table) == {"all kinds", *KINDS}
    assert not COST_KEYS & set().union(*(a | b for a, b in table.values()))


@pytest.mark.parametrize("kind", KINDS)
def test_timeline_record_keys_are_the_documented_ones(records_by_kind, kind):
    table = _documented_keys()
    always = table["all kinds"][0] | table[kind][0]
    allowed = always | table["all kinds"][1] | table[kind][1]
    records = records_by_kind[kind]
    assert records, f"no {kind} record was written"
    for r in records:
        assert always <= set(r), (kind, sorted(always - set(r)))
        assert set(r) <= allowed, (kind, sorted(set(r) - allowed))
        assert not COST_KEYS & set(r)
    # What the table calls "every record" is no wider than the code makes it:
    # a key every record of this run carries is documented as such or applies
    # only where the run happened to have it (a short run's few mixed steps).
    seen_always = set.intersection(*(set(r) for r in records))
    assert seen_always >= always


def test_a_self_drafting_models_records_say_what_its_rounds_drafted_accepted_and_emitted():
    """ISSUE 56: every ``decode`` and ``mixed`` record of a model that drafts with
    its own next-token module carries the program's counts of its rounds, a
    ``decode`` record's ``steps`` are rounds, and the keys are documented ones."""
    import threading

    from inference_gateway_tpu.serving.scheduler import Scheduler, generate_sync

    sched = Scheduler(Engine(EngineConfig(model="openpangu-test-tiny", attention="paged", page_size=16, max_slots=4,
                                          max_seq_len=256, dtype="float32", use_mesh=False, decode_chunk=4)))
    sched.timeline = StepTimeline(512)
    sched.start()
    try:
        threads = [threading.Thread(target=generate_sync, args=(sched, list(range(3, 3 + n))), kwargs={"max_tokens": 14})
                   for n in (8, 40, 9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sched.stop()
    records = [r for r in sched.timeline.tail(None) if r["kind"] in ("decode", "mixed")]
    table = _documented_keys()
    assert {r["kind"] for r in records} == {"decode", "mixed"}
    drafted = accepted = tokens = 0
    for r in records:
        assert {"mtp_drafted", "mtp_accepted", "mtp_tokens"} <= set(r), sorted(r)
        assert r["mtp_drafted"] <= r["mtp_tokens"] == r["mtp_drafted"] + r["mtp_accepted"] <= 2 * r["mtp_drafted"]
        if r["mtp_drafted"]:
            assert r["rows"] >= 1 and r["mtp_drafted"] <= r["rows"] * r["steps"]
        allowed = table["all kinds"][0] | table["all kinds"][1] | table[r["kind"]][0] | table[r["kind"]][1]
        assert set(r) <= allowed, sorted(set(r) - allowed)
        drafted, accepted, tokens = drafted + r["mtp_drafted"], accepted + r["mtp_accepted"], tokens + r["mtp_tokens"]
    assert drafted == sched.spec_slot_rounds > 0 and accepted == sched.spec_accepted and tokens == sched.spec_emitted
    # What the host sent on: every request's 14 tokens, the three first tokens among them.
    assert sum(r["tokens"] for r in records) == 3 * 14


def test_timeline_failures_rate_limited_then_disabled():
    """ISSUE 6 satellite: a broken record path must not logger.error
    once per engine step forever — the scheduler logs the first failure,
    then disables the timeline after 8 consecutive ones, and serving
    continues."""
    from inference_gateway_tpu.serving.scheduler import Scheduler, generate_sync

    class _BrokenTimeline:
        def record(self, *a, **k):
            raise RuntimeError("boom")

    class _CountingLogger:
        def __init__(self):
            self.errors = []

        def error(self, msg, *a):
            self.errors.append(msg)

        def warn(self, *a, **k):
            pass

        def info(self, *a, **k):
            pass

    logger = _CountingLogger()
    sched = Scheduler(Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128, dtype="float32",
                                          max_prefill_batch=2, use_mesh=False)), logger=logger)
    sched.timeline = _BrokenTimeline()
    sched.start()
    try:
        # 96 tokens = ~13 decode chunks + the prefill: comfortably past
        # the 8-consecutive-failures disable threshold.
        out, reason = generate_sync(sched, [1, 2, 3], max_tokens=96)
        assert len(out) > 0  # serving survived the observer
        # Enough steps ran to cross the disable threshold.
        assert sched.timeline is None and not sched._observing
        # Rate limit: first failure + the disable notice, not one per step.
        assert 1 <= len(logger.errors) <= 3, logger.errors
        assert any("disabled" in m for m in logger.errors)
    finally:
        sched.stop()
