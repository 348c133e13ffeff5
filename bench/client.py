"""The load generator: streamed chat completions over asyncio, one thread.

Each request is one HTTP/1.1 connection (independent users share nothing), sent
when due, read frame by frame. A frame's time is the monotonic clock when the
bytes that complete it were read. Only *content* frames count as tokens: the
role preamble leaves before any token exists, and an empty delta carries none.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field

from traffic import Req


@dataclass
class Result:
    index: int
    trace_id: str
    due_t: float = 0.0            # monotonic; the moment the request was due
    sent_t: float = 0.0           # monotonic; when the generator really sent it
    status: int = 0
    error: str | None = None
    first_content_t: float | None = None
    last_content_t: float | None = None
    done_t: float | None = None   # [DONE] read
    content_chars: int = 0
    content_frames: int = 0
    chars_in_window: int = 0
    frames: list = field(default_factory=list)   # (time, tokens) of every content frame
    text: str = ""
    ids: set = field(default_factory=set)
    role_frames: int = 0
    finishes: list = field(default_factory=list)
    usages: list = field(default_factory=list)
    done_last: bool = False
    malformed: str | None = None
    max_tokens: int = 0
    max_gap_s: float = 0.0        # longest wait between two content frames

    @property
    def late_s(self) -> float:
        return self.sent_t - self.due_t


class SSEParser:
    """Incremental ``text/event-stream`` reader: bytes in, ``data:`` payloads out."""

    def __init__(self) -> None:
        self._buf = b""

    def feed(self, data: bytes) -> list[str]:
        self._buf += data
        events = []
        while True:
            cut = self._buf.find(b"\n\n")
            if cut < 0:
                return events
            raw, self._buf = self._buf[:cut], self._buf[cut + 2:]
            lines = [ln[5:].lstrip(b" ") for ln in raw.split(b"\n") if ln.startswith(b"data:")]
            if lines:
                events.append(b"\n".join(lines).decode("utf-8", errors="replace"))

    @property
    def pending(self) -> bytes:
        return self._buf


def apply_event(res: Result, payload: str, now: float, window: tuple[float, float]) -> None:
    """Fold one SSE payload into the request's record."""
    if res.done_last:
        res.malformed = res.malformed or "an event after [DONE]"
    if payload == "[DONE]":
        res.done_last = True
        res.done_t = now
        return
    try:
        doc = json.loads(payload)
    except ValueError:
        res.malformed = res.malformed or f"event is not JSON: {payload[:80]!r}"
        return
    if doc.get("id"):
        res.ids.add(doc["id"])
    if doc.get("usage"):
        res.usages.append(doc["usage"])
    for choice in doc.get("choices") or []:
        delta = choice.get("delta") or {}
        if delta.get("role"):
            res.role_frames += 1
        text = delta.get("content") or ""
        if text:
            if res.first_content_t is None:
                res.first_content_t = now
            else:
                res.max_gap_s = max(res.max_gap_s, now - res.last_content_t)
            res.last_content_t = now
            res.content_frames += 1
            res.content_chars += len(text)
            res.text += text
            res.frames.append((now, len(text)))
            if window[0] <= now < window[1]:
                res.chars_in_window += len(text)
        if choice.get("finish_reason"):
            res.finishes.append(choice["finish_reason"])


async def _read_body(reader: asyncio.StreamReader, headers: dict[str, str], on_data) -> None:
    if "chunked" in headers.get("transfer-encoding", "").lower():
        while True:
            size_line = await reader.readline()
            if not size_line:
                raise ConnectionError("stream ended inside the chunked body")
            size = int(size_line.split(b";")[0].strip() or b"0", 16)
            if size == 0:
                await reader.readline()
                return
            on_data(await reader.readexactly(size))
            await reader.readexactly(2)
    elif "content-length" in headers:
        on_data(await reader.readexactly(int(headers["content-length"])))
    else:
        while True:
            data = await reader.read(65536)
            if not data:
                return
            on_data(data)


async def http_request(port: int, method: str, path: str, payload: bytes | None,
                       extra_headers: dict[str, str], on_data,
                       on_status=None) -> tuple[int, dict[str, str]]:
    """One HTTP/1.1 exchange on a connection of its own. ``on_status`` hears the
    status before any body byte goes to ``on_data``."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        head = [f"{method} {path} HTTP/1.1", f"Host: 127.0.0.1:{port}", "Connection: close",
                "Accept: text/event-stream, application/json"]
        if payload is not None:
            head += ["Content-Type: application/json", f"Content-Length: {len(payload)}"]
        head += [f"{k}: {v}" for k, v in extra_headers.items()]
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + (payload or b""))
        await writer.drain()
        status_line = await reader.readline()
        parts = status_line.split()
        if len(parts) < 2:
            raise ConnectionError(f"no status line: {status_line[:80]!r}")
        status = int(parts[1])
        if on_status is not None:
            on_status(status)
        headers: dict[str, str] = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, value = line.decode("latin-1").partition(":")
            headers[key.strip().lower()] = value.strip()
        await _read_body(reader, headers, on_data)
        return status, headers
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass


def new_trace_id(rng) -> str:
    return "%032x" % rng.getrandbits(128)


async def stream_one(port: int, req: Req, doc: dict, trace_id: str, due_t: float,
                     window: tuple[float, float]) -> Result:
    """Send one streamed completion now; ``due_t`` is when it was due."""
    res = Result(index=req.index, trace_id=trace_id, due_t=due_t, max_tokens=req.max_tokens)
    parser = SSEParser()

    def on_status(status: int) -> None:
        res.status = status

    def on_data(data: bytes) -> None:
        now = time.monotonic()
        if res.status != 200:
            return  # an error body is JSON, not a stream
        for payload in parser.feed(data):
            apply_event(res, payload, now, window)

    span = "%016x" % (int(trace_id[:16], 16) | 1)
    headers = {"traceparent": f"00-{trace_id}-{span}-01"}
    res.sent_t = time.monotonic()
    try:
        status, _ = await http_request(port, "POST", "/v1/chat/completions",
                                       json.dumps(doc).encode(), headers, on_data, on_status)
        if status != 200:
            res.error = f"HTTP {status}"
        elif parser.pending.strip():
            res.malformed = res.malformed or "stream does not end on an event boundary"
    except Exception as e:  # one request's fault is a failed request, never a dead generator
        res.error = f"{type(e).__name__}: {e}"
    return res


async def run_open(port: int, reqs: list[Req], docs: list[dict], trace_ids: list[str],
                   t_open: float, seconds: float, drain_s: float) -> list[Result]:
    """Open loop: every request leaves when due, whatever the earlier ones do."""
    window = (t_open, t_open + seconds)
    tasks = []
    for req, doc, tid in zip(reqs, docs, trace_ids):
        due_t = t_open + req.due_s
        delay = due_t - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(stream_one(port, req, doc, tid, due_t, window)))
    return await _gather_until(tasks, reqs, trace_ids, window[1] + drain_s)


async def run_closed(port: int, reqs: list[Req], docs: list[dict], trace_ids: list[str],
                     clients: int, t_start: float, seconds: float, drain_s: float) -> list[Result]:
    """Closed loop: each client sends its next request when its last one ends,
    from ``t_start`` on, and starts none once ``seconds`` have passed. A
    request's due time is the moment its client was free to send it."""
    window = (t_start, t_start + seconds)
    nxt = iter(range(len(reqs)))
    results: list[Result] = []
    started: list[int] = []

    async def one_client() -> None:
        while time.monotonic() < window[1]:
            i = next(nxt, None)
            if i is None:
                return
            started.append(i)
            results.append(await stream_one(port, reqs[i], docs[i], trace_ids[i],
                                            time.monotonic(), window))

    delay = t_start - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    tasks = [asyncio.ensure_future(one_client()) for _ in range(clients)]
    _, pending = await asyncio.wait(tasks, timeout=max(window[1] + drain_s - time.monotonic(), 0))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    finished = {r.index for r in results}
    for i in started:
        if i not in finished:
            results.append(Result(index=i, trace_id=trace_ids[i], due_t=window[1],
                                  error="still open after the drain", max_tokens=reqs[i].max_tokens))
    return results


async def _gather_until(tasks, reqs, trace_ids, deadline: float) -> list[Result]:
    if not tasks:
        return []
    done, pending = await asyncio.wait(tasks, timeout=max(deadline - time.monotonic(), 0))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    out = []
    for req, tid, t in zip(reqs, trace_ids, tasks):
        if t in done and not t.cancelled() and t.exception() is None:
            out.append(t.result())
        else:
            out.append(Result(index=req.index, trace_id=tid, error="still open after the drain",
                              max_tokens=req.max_tokens))
    return out


def lateness(results: list[Result]) -> dict:
    late = sorted(r.late_s for r in results if r.sent_t)
    if not late:
        return {"n": 0}
    return {"n": len(late), "median_ms": late[len(late) // 2] * 1e3, "max_ms": late[-1] * 1e3}
