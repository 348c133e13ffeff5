"""Post-first-byte stream continuation (ISSUE 9 tentpole).

Three layers, matching the tentpole:

- ``ChatStreamContinuation`` unit behavior: delta accumulation across
  arbitrary block boundaries, the role-preamble splice, completeness and
  overflow disarms.
- Gateway recovery against a continuation-aware scripted upstream on a
  VirtualClock (zero real sleeps): a greedy stream killed after the
  first byte — reset, stall, or kill-right-after-the-preamble — completes
  byte-identical to an unkilled run under one trace id, with every token
  generated exactly once; bounded by RESILIENCE_STREAM_RETRY_MAX and
  disabled by RESILIENCE_CONTINUATION_ENABLED=false.
- The sidecar continuation API against a real engine: a continuation
  request re-prefills prompt+prefix, returns exactly the remaining
  tokens under the original completion id, splices usage to the whole
  logical stream, and bills only the new tokens — plus the full
  gateway→sidecar e2e acceptance with a scripted relay kill at decode
  step N.
"""

import json
import random
from collections import deque

import pytest

from inference_gateway_tpu.config import Config
from inference_gateway_tpu.netio import sse
from inference_gateway_tpu.netio.client import ClientResponse, HTTPClient, HTTPClientError
from inference_gateway_tpu.netio.server import Headers, Request
from inference_gateway_tpu.otel.access_log import AccessLog
from inference_gateway_tpu.otel.otel import OpenTelemetry
from inference_gateway_tpu.providers.registry import ProviderRegistry
from inference_gateway_tpu.providers.routing import Deployment, Pool, Selector
from inference_gateway_tpu.resilience import Resilience, VirtualClock
from inference_gateway_tpu.resilience.continuation import ChatStreamContinuation
from inference_gateway_tpu.resilience.faults import Fault, FaultInjectingClient, FaultScript
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.server import SidecarServer

TRACEPARENT = "00-abcdefabcdefabcdefabcdefabcdef12-1234567890abcdef-01"
DELTAS = ["Hel", "lo", " wor", "ld", ", spliced", " stream", "!"]
PROMPT_TOKENS = 7


# ---------------------------------------------------------------------------
# A continuation-aware scripted upstream: speaks the sidecar's chunk
# shape (role preamble, per-token content frames, finish, usage, DONE),
# honors the ``continuation`` extension by serving only the remaining
# deltas under the echoed id, and plays scripted kills at exact content
# frames — the gateway-level twin of the real sidecar semantics.
# ---------------------------------------------------------------------------
class ContinuationUpstream:
    def __init__(self, clock, *, deltas=None, kills=(), rng=None,
                 model="pool-model") -> None:
        self.clock = clock
        self.deltas = list(deltas if deltas is not None else DELTAS)
        self.kills = deque(kills)  # per successive call: None | ("dead",) | ("reset", n) | ("stall", n)
        self.rng = rng or random.Random(1234)
        self.model = model
        self.calls: list[dict] = []
        self.traceparents: list[str] = []
        self.content_served = 0  # content frames yielded across ALL calls

    # -- HTTPClient shape ------------------------------------------------
    async def request(self, method, url, headers=None, body=b"", timeout=None,
                      stream=False, traceparent=None):
        assert "/chat/completions" in url, url
        parsed = json.loads(body)
        self.calls.append(parsed)
        if traceparent:
            self.traceparents.append(traceparent)
        cont = parsed.get("continuation")
        start = self._resume_index(cont) if cont else 0
        cid = (cont or {}).get("id") or "chatcmpl-fake"
        created = int((cont or {}).get("created") or 111)
        kill = self.kills.popleft() if self.kills else None
        resp = ClientResponse(status=200, headers=Headers())
        resp.headers.set("Content-Type", "text/event-stream")
        resp._inproc_chunks = self._stream(cid, created, start, kill)
        return resp

    async def post(self, url, body, headers=None, timeout=None, stream=False,
                   traceparent=None):
        return await self.request("POST", url, headers=headers, body=body,
                                  timeout=timeout, stream=stream,
                                  traceparent=traceparent)

    async def get(self, url, headers=None, timeout=None, traceparent=None):
        return await self.request("GET", url, headers=headers, timeout=timeout,
                                  traceparent=traceparent)

    # -- internals -------------------------------------------------------
    def _resume_index(self, cont) -> int:
        """Once-only generation invariant: the continuation prefix must
        be a delta-aligned prefix of the canonical stream."""
        text = (cont or {}).get("text") or ""
        joined = ""
        for i, d in enumerate(self.deltas):
            if joined == text:
                return i
            joined += d
        assert joined == text, f"continuation text {text!r} not a served prefix"
        return len(self.deltas)

    def _frames(self, cid, created, start):
        def chunk(delta, finish):
            return sse.format_event({
                "id": cid, "object": "chat.completion.chunk", "created": created,
                "model": self.model,
                "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
            })

        frames = [(False, chunk({"role": "assistant", "content": ""}, None))]
        for d in self.deltas[start:]:
            frames.append((True, chunk({"content": d}, None)))
        frames.append((False, chunk({}, "stop")))
        total = len(self.deltas)
        frames.append((False, sse.format_event({
            "id": cid, "object": "chat.completion.chunk", "created": created,
            "model": self.model, "choices": [],
            "usage": {"prompt_tokens": PROMPT_TOKENS, "completion_tokens": total,
                      "total_tokens": PROMPT_TOKENS + total},
        })))
        frames.append((False, sse.DONE_FRAME))
        return frames

    async def _stream(self, cid, created, start, kill):
        if kill is not None and kill[0] == "dead":
            raise HTTPClientError("injected dead upstream (no bytes)")
        frames = self._frames(cid, created, start)
        mode = None
        if kill is not None:
            mode, n = kill
            out, content = [], 0
            for is_content, fb in frames:
                if is_content and content >= n:
                    break
                out.append((is_content, fb))
                if is_content:
                    content += 1
            frames = out
        self.content_served += sum(1 for ic, _fb in frames if ic)
        blob = b"".join(fb for _ic, fb in frames)
        # Random block boundaries: the continuation's line reassembly and
        # the splice's frame scan must survive arbitrary chopping.
        i = 0
        while i < len(blob):
            j = i + self.rng.randint(1, 37)
            yield blob[i:j]
            i = j
        if mode == "stall":
            await self.clock.sleep(600.0)  # virtually past any idle timeout
            raise HTTPClientError("injected stall-then-reset")
        if mode == "reset":
            raise HTTPClientError("injected mid-stream reset")


def _make_router(upstream, env=None, otel=None, n_candidates=3):
    from inference_gateway_tpu.api.routes import RouterImpl

    clk = upstream.clock
    cfg = Config.load(env or {})
    registry = ProviderRegistry({"tpu": cfg.providers["tpu"]})
    res = Resilience(cfg.resilience, otel=otel, clock=clk, rng=random.Random(0))
    pools = {"pool-model": Pool("pool-model", [
        Deployment("tpu", f"model-{chr(ord('a') + i)}") for i in range(n_candidates)])}
    selector = Selector(pools, health=res.healthy)
    return RouterImpl(cfg, registry, upstream, otel=otel, selector=selector,
                      resilience=res), res


def _post_chat_stream(model="pool-model", include_usage=True) -> Request:
    body = {"model": model, "stream": True, "temperature": 0,
            "messages": [{"role": "user", "content": "x"}]}
    if include_usage:
        body["stream_options"] = {"include_usage": True}
    req = Request(method="POST", path="/v1/chat/completions", query={},
                  headers=Headers(), body=json.dumps(body).encode())
    req.ctx["traceparent"] = TRACEPARENT
    return req


async def _drain(resp) -> bytes:
    out = b""
    async for chunk in resp.chunks:
        out += chunk
    return out


async def _baseline() -> bytes:
    clk = VirtualClock()
    upstream = ContinuationUpstream(clk)
    router, _ = _make_router(upstream)
    resp = await router.chat_completions_handler(_post_chat_stream())
    assert resp.status == 200
    return await _drain(resp)


# ---------------------------------------------------------------------------
# ChatStreamContinuation unit behavior
# ---------------------------------------------------------------------------
def _frame(obj) -> bytes:
    return sse.format_event(obj)


def test_continuation_accumulates_across_block_boundaries():
    cont = ChatStreamContinuation(lambda c, b, p: None)
    blob = _frame({"id": "cmpl-1", "created": 5, "model": "m",
                   "choices": [{"index": 0, "delta": {"role": "assistant", "content": ""},
                                "finish_reason": None}]})
    blob += _frame({"id": "cmpl-1", "created": 5, "model": "m",
                    "choices": [{"index": 0, "delta": {"content": "ab"},
                                 "finish_reason": None}]})
    blob += _frame({"id": "cmpl-1", "created": 5, "model": "m",
                    "choices": [{"index": 0, "delta": {"content": "cd"},
                                 "finish_reason": None}]})
    # Feed one byte at a time: partial-line reassembly must be exact.
    for i in range(len(blob)):
        cont.observe(blob[i:i + 1])
    assert cont.text == "abcd"
    assert cont.frames == 2
    assert cont.completion_id == "cmpl-1"
    assert cont.created == 5
    assert cont.can_resume()
    payload = cont.payload()
    assert payload == {"text": "abcd", "emitted_tokens": 2, "id": "cmpl-1",
                       "created": 5}


def test_continuation_accepts_crlf_frame_separators():
    """Review regression: spec-legal CRLF event separators must complete
    frames (an LF-only scan never fires, silently disarming the
    continuation while _buf grows)."""
    cont = ChatStreamContinuation(lambda c, b, p: None)
    frame = (b'data: {"id":"crlf-1","created":3,"model":"m","choices":'
             b'[{"index":0,"delta":{"content":"ok"},"finish_reason":null}]}\r\n\r\n')
    for i in range(len(frame)):
        cont.observe(frame[i:i + 1])
    assert cont.completion_id == "crlf-1"
    assert cont.text == "ok"
    assert cont.pending_raw == b""
    assert cont.can_resume()


def test_continuation_completes_on_finish_or_done():
    for terminal in (
        _frame({"id": "x", "choices": [{"index": 0, "delta": {},
                                        "finish_reason": "stop"}]}),
        sse.DONE_FRAME,
    ):
        cont = ChatStreamContinuation(lambda c, b, p: None)
        cont.observe(_frame({"id": "x", "choices": [
            {"index": 0, "delta": {"content": "a"}, "finish_reason": None}]}))
        assert cont.can_resume()
        cont.observe(terminal)
        assert cont.complete and not cont.can_resume()


def test_continuation_overflow_disarms():
    cont = ChatStreamContinuation(lambda c, b, p: None, max_buffer=256)
    cont.observe(_frame({"id": "x", "choices": [
        {"index": 0, "delta": {"content": "y" * 300}, "finish_reason": None}]}))
    assert cont.overflowed and not cont.can_resume()


async def test_splice_suppresses_only_the_role_preamble():
    cont = ChatStreamContinuation(lambda c, b, p: None)
    role = _frame({"id": "x", "choices": [{"index": 0,
                                           "delta": {"role": "assistant", "content": ""},
                                           "finish_reason": None}]})
    content = _frame({"id": "x", "choices": [{"index": 0, "delta": {"content": "hi"},
                                              "finish_reason": None}]})

    async def feed(chunks):
        for c in chunks:
            yield c

    # Role frame split across blocks + content in the same block.
    out = b""
    async for chunk in cont.splice(feed([role[:7], role[7:] + content, content])):
        out += chunk
    assert out == content + content

    # No preamble (already suppressed upstream?) — nothing is dropped.
    cont2 = ChatStreamContinuation(lambda c, b, p: None)
    out2 = b""
    async for chunk in cont2.splice(feed([content])):
        out2 += chunk
    assert out2 == content


async def test_splice_discards_client_held_bytes_on_early_close():
    """Review regression: a continued stream that closes cleanly while
    still inside the pending-trim stage must NOT re-emit the bytes the
    client already holds — and the continuation state must stay intact
    for a further hop."""
    cont = ChatStreamContinuation(lambda c, b, p: None)
    role = _frame({"id": "x", "choices": [{"index": 0,
                                           "delta": {"role": "assistant", "content": ""},
                                           "finish_reason": None}]})
    f1 = _frame({"id": "x", "choices": [{"index": 0, "delta": {"content": "a"},
                                         "finish_reason": None}]})
    f2 = _frame({"id": "x", "choices": [{"index": 0, "delta": {"content": "b"},
                                         "finish_reason": None}]})
    # The client holds role + f1 + the first 12 bytes of f2.
    cont.observe(role + f1 + f2[:12])
    assert cont.pending_raw == f2[:12]

    async def feed(chunks):
        for c in chunks:
            yield c

    # Continued stream relays the preamble + only 5 bytes of the
    # re-framed token, then dies cleanly: nothing may reach the client.
    out = b""
    async for chunk in cont.splice(feed([role, f2[:5]])):
        out += chunk
    assert out == b""
    assert cont.pending_raw == f2[:12]  # unchanged — next hop still exact

    # And the next hop that survives splices correctly.
    out2 = b""
    async for chunk in cont.splice(feed([role + f2])):
        out2 += chunk
    assert out2 == f2[12:]


async def test_splice_mismatch_closes_dangling_frame_before_passthrough():
    """Review regression: when the resumed stream's first frame does NOT
    match the client's dangling partial frame (resampled stream,
    different coalescing), the splice must terminate the partial frame
    (``\\n\\n``) before passing through — otherwise the two concatenate
    into one garbled SSE event — and observe() must stay parseable."""
    cont = ChatStreamContinuation(lambda c, b, p: None)
    role = _frame({"id": "x", "choices": [{"index": 0,
                                           "delta": {"role": "assistant", "content": ""},
                                           "finish_reason": None}]})
    f2 = _frame({"id": "x", "choices": [{"index": 0, "delta": {"content": "bb"},
                                         "finish_reason": None}]})
    other = _frame({"id": "x", "choices": [{"index": 0, "delta": {"content": "ZZ"},
                                            "finish_reason": None}]})
    # The client's dangling partial frame extends PAST the shared chunk
    # envelope into the delta content ("bb"), so the resumed frame
    # ("ZZ") genuinely diverges from it. (A partial that stops inside
    # the shared envelope prefix trims cleanly — held bytes + remainder
    # still form exactly the new frame — and is not a mismatch.)
    cont.observe(role + f2[:-4])

    async def feed(chunks):
        for c in chunks:
            yield c

    out = b""
    async for chunk in cont.splice(feed([role + other])):
        out += chunk
    assert out == b"\n\n" + other  # partial frame closed, then verbatim
    # The same bytes keep observe() consistent: the garbled closed frame
    # is ignored, the mismatched frame parses — text stays well-formed.
    cont.observe(out)
    assert cont.text == "ZZ"
    assert cont.pending_raw == b""


# ---------------------------------------------------------------------------
# Gateway recovery with the continuation-aware upstream (VirtualClock)
# ---------------------------------------------------------------------------
async def test_post_first_byte_kill_splices_byte_identical():
    """Acceptance (gateway half): a greedy stream killed after 3 relayed
    tokens completes byte-identical to the unkilled run — one trace id,
    once-only token generation, post_first_byte recovery counted."""
    unkilled = await _baseline()
    assert sse.DONE_FRAME in unkilled

    otel = OpenTelemetry()
    clk = VirtualClock()
    upstream = ContinuationUpstream(clk, kills=[("reset", 3)])
    router, _res = _make_router(upstream, otel=otel)
    resp = await router.chat_completions_handler(_post_chat_stream())
    assert resp.status == 200
    body = await _drain(resp)
    assert body == unkilled

    # The continuation request carried the relayed prefix and the
    # original envelope identity.
    assert len(upstream.calls) == 2
    cont = upstream.calls[1]["continuation"]
    assert cont["text"] == "".join(DELTAS[:3])
    assert cont["id"] == "chatcmpl-fake" and cont["created"] == 111
    # Once-only generation: 3 relayed + the remainder, no token twice.
    assert upstream.content_served == len(DELTAS)
    # One trace id across the kill.
    assert set(upstream.traceparents) == {TRACEPARENT}
    vals = otel.streams_recovered_counter.values()
    assert sum(vals.values()) == 1
    assert vals[("pool-model", "tpu", "tpu", "post_first_byte")] == 1


async def test_kill_right_after_preamble_still_splices():
    """Death after the role chunk but before any content (relayed bytes,
    empty prefix): the continuation resumes from token zero."""
    unkilled = await _baseline()
    clk = VirtualClock()
    upstream = ContinuationUpstream(clk, kills=[("reset", 0)])
    router, _ = _make_router(upstream)
    body = await _drain(await router.chat_completions_handler(_post_chat_stream()))
    assert body == unkilled
    assert upstream.calls[1]["continuation"]["text"] == ""


async def test_mid_stream_stall_feeds_continuation():
    """ISSUE 9 satellite: a stalled upstream after the first byte no
    longer raises into the client stream — with a continuation it
    recovers exactly like a reset."""
    unkilled = await _baseline()
    otel = OpenTelemetry()
    clk = VirtualClock()
    upstream = ContinuationUpstream(clk, kills=[("stall", 2)])
    router, _ = _make_router(upstream, otel=otel)
    body = await _drain(await router.chat_completions_handler(_post_chat_stream()))
    assert body == unkilled
    vals = otel.streams_recovered_counter.values()
    assert vals[("pool-model", "tpu", "tpu", "post_first_byte")] == 1


async def test_two_kills_within_retry_max_splice_twice():
    unkilled = await _baseline()
    otel = OpenTelemetry()
    clk = VirtualClock()
    upstream = ContinuationUpstream(clk, kills=[("reset", 2), ("reset", 2)])
    router, _ = _make_router(upstream, otel=otel)
    body = await _drain(await router.chat_completions_handler(_post_chat_stream()))
    assert body == unkilled
    assert len(upstream.calls) == 3
    # Second continuation resumes from the TOTAL relayed prefix (2 + 2).
    assert upstream.calls[2]["continuation"]["text"] == "".join(DELTAS[:4])
    assert upstream.content_served == len(DELTAS)
    vals = otel.streams_recovered_counter.values()
    assert vals[("pool-model", "tpu", "tpu", "post_first_byte")] == 2


async def test_retry_max_exhaustion_truncates_cleanly():
    """Past RESILIENCE_STREAM_RETRY_MAX the stream ends truncated (no
    [DONE], no exception raised into bytes already framed)."""
    unkilled = await _baseline()
    clk = VirtualClock()
    upstream = ContinuationUpstream(
        clk, kills=[("reset", 2), ("reset", 1), ("reset", 1), ("reset", 1)])
    router, _ = _make_router(upstream, n_candidates=5)
    body = await _drain(await router.chat_completions_handler(_post_chat_stream()))
    assert sse.DONE_FRAME not in body
    assert unkilled.startswith(body)  # a clean prefix, never garbage


async def test_continuation_kill_switch_restores_truncation():
    clk = VirtualClock()
    otel = OpenTelemetry()
    upstream = ContinuationUpstream(clk, kills=[("reset", 3)])
    router, _ = _make_router(upstream, otel=otel,
                             env={"RESILIENCE_CONTINUATION_ENABLED": "false"})
    body = await _drain(await router.chat_completions_handler(_post_chat_stream()))
    assert sse.DONE_FRAME not in body
    assert len(upstream.calls) == 1  # no continuation request issued
    assert sum(otel.streams_recovered_counter.values().values()) == 0


async def test_pre_first_byte_death_still_reissues_full_request():
    """The PR 7 contract is unchanged: zero bytes relayed → the request
    is re-ISSUED (no continuation extension), counted pre_first_byte."""
    unkilled = await _baseline()
    otel = OpenTelemetry()
    clk = VirtualClock()
    upstream = ContinuationUpstream(clk, kills=[("dead",)])
    router, _ = _make_router(upstream, otel=otel)
    body = await _drain(await router.chat_completions_handler(_post_chat_stream()))
    assert body == unkilled
    assert "continuation" not in upstream.calls[1]
    vals = otel.streams_recovered_counter.values()
    assert vals[("pool-model", "tpu", "tpu", "pre_first_byte")] == 1


async def test_usage_across_kill_equals_unkilled():
    """ISSUE 9 satellite (continuation accounting): the client-visible
    usage of a killed-and-continued stream equals the unkilled run's."""
    def usage_of(body: bytes):
        for payload in sse.split_sse_payloads(body):
            event = json.loads(payload)
            if event.get("usage"):
                return event["usage"]
        return None

    unkilled = await _baseline()
    clk = VirtualClock()
    upstream = ContinuationUpstream(clk, kills=[("reset", 4)])
    router, _ = _make_router(upstream)
    body = await _drain(await router.chat_completions_handler(_post_chat_stream()))
    expected = usage_of(unkilled)
    assert expected is not None
    assert usage_of(body) == expected
    assert expected["completion_tokens"] == len(DELTAS)


# ---------------------------------------------------------------------------
# Sidecar continuation API against a real engine
# ---------------------------------------------------------------------------
def test_seed_detok_single_pass_matches_push_replay():
    """Review regression: _seed_detok seeds in one decode pass; its
    final state must equal the per-token push() replay (including the
    trailing partial-UTF-8 holdback) so continued deltas still match."""
    from inference_gateway_tpu.serving.tokenizer import ByteTokenizer, DetokenizeState

    tok = ByteTokenizer()
    # "héllo" UTF-8 plus a dangling lead byte of a multi-byte sequence.
    ids = list("héllo".encode("utf-8")) + [0xE4]
    replay = DetokenizeState()
    for t in ids:
        replay.push(tok, t)

    class _Sidecar:
        class engine:
            tokenizer = tok
    seeded = SidecarServer._seed_detok(_Sidecar(), {"resume_ids": ids})
    assert seeded.ids == replay.ids
    assert seeded.emitted == replay.emitted == "héllo"


@pytest.fixture(scope="module")
def sidecar_stack(aloop):
    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False,
                                 decode_chunk=2))
    access_log = AccessLog(service="tpu-sidecar", tail_size=64)
    sidecar = SidecarServer(engine, served_model_name="test-tiny",
                            access_log=access_log)
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    yield sidecar, port, access_log
    aloop.run(sidecar.shutdown())


async def _sidecar_stream_raw(port, body: dict) -> bytes:
    client = HTTPClient()
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             json.dumps(body).encode(), stream=True)
    assert resp.status == 200
    out = b""
    async for block in resp.iter_raw():
        out += block
    return out


def _chat_body(max_tokens=8, **extra) -> dict:
    return {"model": "test-tiny", "stream": True, "temperature": 0,
            "max_tokens": max_tokens,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user", "content": "splice me"}], **extra}


def _parse_frames(body: bytes):
    """(payload_bytes, parsed) pairs for each data frame, [DONE] kept."""
    frames = []
    for part in body.split(b"\n\n"):
        part = part.strip()
        if not part.startswith(b"data:"):
            continue
        payload = part[5:].strip()
        frames.append((part + b"\n\n",
                       None if payload == b"[DONE]" else json.loads(payload)))
    return frames


async def test_sidecar_continuation_resumes_byte_identical(sidecar_stack):
    """Acceptance (sidecar half): a continuation request whose text is
    the first k deltas returns EXACTLY the remaining frames of the full
    run under the original id — byte-identical past the role preamble —
    with usage spanning the whole logical stream and only the new
    tokens billed."""
    sidecar, port, access_log = sidecar_stack
    full = await _sidecar_stream_raw(port, _chat_body())
    frames = _parse_frames(full)
    content = [(raw, ev) for raw, ev in frames
               if ev and ev.get("choices") and (ev["choices"][0].get("delta") or {}).get("content")]
    assert len(content) >= 4, "need enough greedy tokens to split"
    usage_full = next(ev["usage"] for _raw, ev in frames if ev and ev.get("usage"))
    cid = frames[0][1]["id"]
    created = frames[0][1]["created"]

    k = 2
    prefix = "".join((ev["choices"][0]["delta"] or {}).get("content", "")
                     for _raw, ev in content[:k])
    continued = await _sidecar_stream_raw(port, _chat_body(continuation={
        "text": prefix, "id": cid, "created": created, "emitted_tokens": k}))

    # Byte-identity past the preamble: continued == role chunk + the
    # full run's frames after the first k content frames. (Frame
    # reconstruction is lossless — sanity-pinned — so splicing the
    # expected bytes from the parsed frame list is exact.)
    assert b"".join(raw for raw, _ev in frames) == full
    cont_frames = _parse_frames(continued)
    _role_raw, role_ev = cont_frames[0]
    assert (role_ev["choices"][0]["delta"] or {}).get("role") == "assistant"
    assert role_ev["id"] == cid and role_ev["created"] == created
    content_positions = [i for i, (_raw, ev) in enumerate(frames)
                         if ev and ev.get("choices")
                         and (ev["choices"][0].get("delta") or {}).get("content")]
    cut_i = content_positions[k - 1]
    assert continued == frames[0][0] + b"".join(raw for raw, _ev in frames[cut_i + 1:])

    # Usage spans the whole logical stream...
    usage_cont = next(ev["usage"] for _raw, ev in cont_frames if ev and ev.get("usage"))
    assert usage_cont == usage_full
    # ...but only the NEW tokens are billed by this replica.
    lines = [e for e in access_log.tail if e.get("route") == "/v1/chat/completions"]
    assert lines[-1]["output_tokens"] == usage_full["completion_tokens"] - k
    assert lines[-1]["input_tokens"] == usage_full["prompt_tokens"]
    assert (lines[-2]["output_tokens"] == usage_full["completion_tokens"])


async def test_sidecar_continuation_token_ids_equivalent_to_text(sidecar_stack):
    """token_ids is the authoritative resume form; for a prefix whose
    encoding round-trips (ASCII here) the two forms must produce
    byte-identical continued streams — same resume point, same usage
    splice, same envelope."""
    sidecar, port, _access_log = sidecar_stack
    prefix = "ab"
    ids = sidecar.engine.tokenizer.encode(prefix, add_bos=False)
    assert len(ids) == 2  # byte tokenizer: 1 byte = 1 token
    by_text = await _sidecar_stream_raw(port, _chat_body(max_tokens=5, continuation={
        "text": prefix, "id": "chatcmpl-eq", "created": 7}))
    by_ids = await _sidecar_stream_raw(port, _chat_body(max_tokens=5, continuation={
        "token_ids": ids, "id": "chatcmpl-eq", "created": 7}))
    assert by_text == by_ids
    frames = _parse_frames(by_ids)
    assert frames[0][1]["id"] == "chatcmpl-eq" and frames[0][1]["created"] == 7
    usage = next(ev["usage"] for _raw, ev in frames if ev and ev.get("usage"))
    # max_tokens spans the whole logical stream: 2 resumed + 3 new.
    assert usage["completion_tokens"] == 5


# ---------------------------------------------------------------------------
# E2E acceptance: gateway → /proxy → sidecar, relay killed at decode
# step N, spliced stream byte-identical under one trace id.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def e2e_stack(aloop, tmp_path_factory):
    from inference_gateway_tpu.main import build_gateway

    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False,
                                 decode_chunk=2))
    access_log = AccessLog(service="tpu-sidecar", tail_size=64)
    sidecar = SidecarServer(engine, served_model_name="test-tiny",
                            access_log=access_log)
    sidecar_port = aloop.run(sidecar.start("127.0.0.1", 0))

    pools_yaml = tmp_path_factory.mktemp("pools") / "pools.yaml"
    pools_yaml.write_text(
        "pools:\n"
        "  - model: pool-tiny\n"
        "    deployments:\n"
        "      - {provider: tpu, model: test-tiny}\n"
        "      - {provider: tpu, model: test-tiny}\n"
    )
    env = {
        "TPU_API_URL": f"http://127.0.0.1:{sidecar_port}/v1",
        "ROUTING_ENABLED": "true",
        "ROUTING_CONFIG_PATH": str(pools_yaml),
        "SERVER_PORT": "0",
        # Tracing on so the edge traceparent rides both establishments
        # (the one-trace-id acceptance assertion).
        "TELEMETRY_ENABLE": "true",
        "TELEMETRY_TRACING_ENABLE": "true",
        "TELEMETRY_METRICS_PORT": "0",
        # Probing would need the pool target healthy before first use;
        # the e2e exercises the continuation path, probing has its own
        # tests — keep the surfaces independent here.
        "RESILIENCE_PROBE_ENABLED": "false",
    }
    gw = build_gateway(env=env)
    gw_port = aloop.run(gw.start("127.0.0.1", 0))
    yield gw, gw_port, sidecar, access_log
    aloop.run(gw.shutdown())
    aloop.run(sidecar.shutdown())


async def _gateway_stream_raw(port, body: dict, traceparent=TRACEPARENT) -> bytes:
    client = HTTPClient()
    headers = Headers()
    headers.set("Content-Type", "application/json")
    if traceparent:
        headers.set("traceparent", traceparent)
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             json.dumps(body).encode(), headers=headers, stream=True)
    assert resp.status == 200
    out = b""
    async for block in resp.iter_raw():
        out += block
    return out


async def test_e2e_mid_stream_kill_byte_identical(e2e_stack):
    """THE acceptance e2e: killing the serving upstream's relay after
    the first byte (at decode step N) on a greedy request yields a
    client stream byte-identical to the unkilled run, under one trace
    id, with continuation tokens billed exactly once."""
    gw, port, sidecar, access_log = e2e_stack
    # The gateway resumes from the TEXT it relayed, which the sidecar
    # re-encodes — so the prefix must round-trip through the byte
    # tokenizer. Random weights emit any byte, and an invalid UTF-8 byte
    # decodes to U+FFFD (three bytes re-encoded): which bytes a seed
    # gives changes with the JAX version. Banning ids 128..255 keeps the
    # greedy stream ASCII, which round-trips by construction.
    body = _chat_body(logit_bias={str(i): -100 for i in range(128, 256)})
    body["model"] = "pool-tiny"

    unkilled = await _gateway_stream_raw(port, body)
    assert sse.DONE_FRAME in unkilled
    usage = next(ev["usage"] for _raw, ev in _parse_frames(unkilled)
                 if ev and ev.get("usage"))
    assert usage["completion_tokens"] >= 4

    # Kill the gateway↔sidecar relay after 4 SSE frames (role + 3
    # content ≈ decode step 3); the continuation re-establishes on the
    # pool's second candidate. Wrap only the provider-facing client.
    script = (FaultScript()
              .script("/proxy/tpu/", Fault.cut_stream(after_frames=4))
              .default("/proxy/tpu/", Fault.passthrough()))
    real_client = gw.router_impl.client
    gw.router_impl.client = FaultInjectingClient(script, inner=real_client)
    try:
        killed = await _gateway_stream_raw(port, body)
    finally:
        gw.router_impl.client = real_client

    # Byte-identity modulo the per-run envelope identity: two separate
    # runs necessarily mint different completion ids/created stamps, so
    # normalize those two fields — everything else (frame shapes, every
    # delta, finish, usage) must match byte-for-byte. Within the killed
    # run, ONE id spans the kill (the splice keeps the original).
    def normalize(raw: bytes) -> bytes:
        frames = _parse_frames(raw)
        ids = {ev["id"] for _r, ev in frames if ev and ev.get("id")}
        created = {ev["created"] for _r, ev in frames if ev and "created" in ev}
        assert len(ids) == 1 and len(created) == 1, (ids, created)
        return (raw.replace(ids.pop().encode(), b"ID")
                   .replace(b'"created":%d' % created.pop(), b'"created":0'))

    assert normalize(killed) == normalize(unkilled)
    kinds = [k for _t, k, _u in script.log]
    assert kinds[0] == "cut" and "passthrough" in kinds
    # Once-only billing: the continuation request's sidecar line bills
    # exactly the tokens past the relayed prefix. The relayed prefix is
    # the first 3 content frames' text (role + 3 content frames were
    # cut through) — re-encoded by the sidecar, so the expected resume
    # token count is its encoding length, not the frame count (one
    # frame can flush several tokens' worth of assembled UTF-8). The
    # killed attempt's own line (the relay died, not the engine) is
    # disconnect-attributed asynchronously, so only the continuation
    # line is asserted exactly.
    deltas = [(ev["choices"][0].get("delta") or {}).get("content")
              for _raw, ev in _parse_frames(unkilled) if ev and ev.get("choices")]
    prefix = "".join(d for d in deltas if d)[: sum(
        len(d) for d in [d for d in deltas if d][:3])]
    resume = len(sidecar.engine.tokenizer.encode(prefix, add_bos=False))
    lines = [e for e in access_log.tail if e.get("route") == "/v1/chat/completions"]
    assert any(e["output_tokens"] == usage["completion_tokens"] - resume
               for e in lines)
    assert 0 < resume < usage["completion_tokens"]


async def test_e2e_trace_id_spans_the_kill(e2e_stack):
    """Both upstream establishments (original + continuation) carry the
    edge request's traceparent."""
    gw, port, _sidecar, _access_log = e2e_stack
    body = _chat_body()
    body["model"] = "pool-tiny"
    script = (FaultScript()
              .script("/proxy/tpu/", Fault.cut_stream(after_frames=4))
              .default("/proxy/tpu/", Fault.passthrough()))
    real_client = gw.router_impl.client
    fault_client = FaultInjectingClient(script, inner=real_client)
    gw.router_impl.client = fault_client
    try:
        killed = await _gateway_stream_raw(port, body)
    finally:
        gw.router_impl.client = real_client
    assert sse.DONE_FRAME in killed
    chat_tps = [tp for url, tp in fault_client.traceparents
                if "/chat/completions" in url]
    assert len(chat_tps) == 2
    trace_ids = {tp.split("-")[1] for tp in chat_tps}
    assert trace_ids == {TRACEPARENT.split("-")[1]}
