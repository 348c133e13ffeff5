#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still starts on the chip.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # the tensor-parallel path on a four-chip host, and only it
    python chip_smoke.py --rehearse-cpu [--chips 4]   # control-flow rehearsal, tiny model, CPU

One chip: client → gateway /v1/chat/completions → tpu provider → /proxy hop →
sidecar CLI → scheduler → engine → Pallas kernels, serving Mistral-7B at its
published width and depth (int8 weights from the seed, paged bf16 KV cache).
Phases, each in processes of its own so that one process holds the chip at a time:

  kernels  the decode, ragged and flash-prefill kernels, compiled, at the serve
           phase's shapes, each against its pure-JAX twin
  serve    sidecar + gateway by their CLIs, a handful of chat completions through
           the gateway, then the sidecar's own account of itself
  restart  the sidecar again, straight, from the now-filled compile cache: its
           warmup seconds beside the first start's (cold only if the cache was
           empty at the start, which the first line says), how many entries
           the restart had to add (0 = every program was a hit), and the same
           temperature-0 answer as before the restart

This parent never imports JAX. The last line of stdout is the result object and
nothing else; any failed phase, dead child or timeout makes it {"ok": false, ...}
with a non-zero exit. Without a chip the children fail to bind the TPU and so
does the run — it never carries on on the CPU. ``--rehearse-cpu`` is explicit,
names the platform it really ran on in its last line, and is never entered
otherwise.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included
T0 = time.monotonic()

# What is served. No width or depth of the model is cut; slots x context are
# sized so that weights + page pool fill the chip's 16 GiB as profiles.hbm_plan
# budgets a deployment (24 x 2,048 tokens = a 6 GiB pool beside ~7 GiB of int8
# weights). The rehearsal swaps in the tiny test model and says so.
REAL = {
    "model": "mistral-7b",
    "platform": "tpu",
    "sidecar_args": ["--quantize", "int8", "--attention", "paged", "--page-size", "128",
                     "--max-slots", "24", "--max-seq-len", "2048", "--dtype", "bfloat16"],
    "env": {},
    "long_prompt_chars": 1500,   # > largest prefill bucket (512) + slots: chunked mixed steps
    "kernel_shapes": {"Hq": 32, "Hkv": 8, "D": 128, "page": 128, "slots": 24, "seq": 2048,
                      "layers": 32, "bucket": 512, "window": 4096, "dtype": "bfloat16"},
    # The decode kernel at the other served layouts (ISSUE 37: its pages a visit
    # follow the layout): name -> (Hq, Hkv, slots, window, the window as the operand).
    "decode_layouts": {"qwen2.5-7b": (28, 4, 48, None, False),
                       "smallthinker-21b-a3b windowed": (28, 4, 48, 4096, True),
                       "phi-4-mini-flash windowed": (40, 10, 64, 512, True),
                       "phi-4-mini-flash full": (40, 10, 64, None, True)},
}
REHEARSAL = {
    "model": "test-tiny",
    "platform": "cpu",
    "sidecar_args": ["--attention", "paged", "--page-size", "16", "--max-slots", "4",
                     "--max-seq-len", "512", "--dtype", "float32"],
    # The tiny model's context is 512 tokens, so a smaller packed budget is what
    # makes the long prompt take more than one mixed step here.
    "env": {"SERVING_MIXED_STEP_TOKENS": "64"},
    "long_prompt_chars": 300,
    "kernel_shapes": {"Hq": 4, "Hkv": 2, "D": 16, "page": 16, "slots": 4, "seq": 128,
                      "layers": 2, "bucket": 32, "window": 64, "dtype": "float32"},
    "decode_layouts": {"six heads over three": (6, 3, 4, None, False),
                       "windowed by the operand": (4, 2, 4, 40, True)},
}
# Kernel vs twin, compared in float32: |a - b| <= ATOL + RTOL * |b|. bf16 keeps 8
# bits of mantissa; kernels and twins round the probabilities to bf16 before the
# PV matmul and sum in another order, which is the whole of the difference.
KERNEL_ATOL, KERNEL_RTOL = 2e-2, 2e-2
# tp = 4 against one device, same weights: the first generated token's logprob
# (the next-token distribution after prefill) per request. Sharding changes the
# order of the bf16 reductions, nothing else.
TP_LOGPROB_TOL = 0.1


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - T0)


def say(**fields) -> None:
    """One earlier line of the report (JSON, flushed)."""
    print(json.dumps({"t": round(time.monotonic() - T0, 1), **fields}), flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Child processes
# ---------------------------------------------------------------------------
_children: list[subprocess.Popen] = []


def spawn(name: str, argv: list[str], env: dict[str, str]) -> subprocess.Popen:
    OUT.mkdir(parents=True, exist_ok=True)
    log = open(OUT / f"{name}.log", "wb")
    proc = subprocess.Popen(argv, cwd=ROOT, env={**os.environ, **env}, stdout=log,
                            stderr=subprocess.STDOUT, start_new_session=True)
    log.close()
    _children.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace: float = 30.0) -> None:
    """SIGTERM the child's process group, SIGKILL what outlives the grace."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
            proc.wait(timeout=grace)
        except (ProcessLookupError, subprocess.TimeoutExpired):
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def stop_all() -> None:
    for proc in reversed(_children):
        stop(proc, grace=10.0)


def log_tail(name: str, n: int = 25) -> list[str]:
    try:
        return (OUT / f"{name}.log").read_text(errors="replace").splitlines()[-n:]
    except OSError:
        return []


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 30.0) -> tuple[int, dict | None, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=payload,
                     headers={"Content-Type": "application/json"} if payload else {})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    try:
        return resp.status, json.loads(raw), raw
    except ValueError:
        return resp.status, None, raw


def wait_healthy(name: str, proc: subprocess.Popen, port: int, limit: float) -> float:
    """Poll /health until 200; fail when the child dies or the limit passes."""
    t0 = time.monotonic()
    while True:
        if proc.poll() is not None:
            raise SmokeFailure(f"{name} exited with code {proc.returncode} before it was "
                               f"healthy: {log_tail(name, 8)}")
        try:
            status, _, _ = http_json(port, "GET", "/health", timeout=5.0)
            if status == 200:
                return time.monotonic() - t0
        except OSError:
            pass
        if time.monotonic() - t0 > min(limit, remaining()):
            raise SmokeFailure(f"{name} not healthy after {limit:.0f}s: {log_tail(name, 8)}")
        time.sleep(1.0)


def log_records(name: str) -> list[dict]:
    out = []
    for line in (OUT / f"{name}.log").read_text(errors="replace").splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def cache_entries(path: str | None) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for _ in os.scandir(path))


def expected_cache_dir() -> str:
    """utils/platform.configure_compile_cache's rule, restated: the variable if
    set, else a fixed directory in the checkout. The sidecar must name this one."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")


# ---------------------------------------------------------------------------
# Requests and what a right answer looks like
# ---------------------------------------------------------------------------
def chat_body(cfg: dict, prompt: str, max_tokens: int, **extra) -> dict:
    return {"model": f"tpu/{cfg['model']}", "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_tokens, "temperature": 0, **extra}


def check_usage(usage: dict, finish: str, max_tokens: int, where: str) -> None:
    check(isinstance(usage, dict), f"{where}: no usage")
    done, asked = usage.get("completion_tokens"), usage.get("prompt_tokens")
    check(isinstance(done, int) and isinstance(asked, int) and asked > 0,
          f"{where}: usage not integers: {usage}")
    check(1 <= done <= max_tokens, f"{where}: completion_tokens {done} outside 1..{max_tokens}")
    check(usage.get("total_tokens") == asked + done, f"{where}: total_tokens does not add up: {usage}")
    check(finish in ("stop", "length"), f"{where}: finish_reason {finish!r}")
    if finish == "length":
        check(done == max_tokens, f"{where}: finish_reason length at {done} of {max_tokens} tokens")


def complete(port: int, body: dict, where: str) -> dict:
    """One non-streamed completion, checked. Returns text, logprobs, usage."""
    status, doc, raw = http_json(port, "POST", "/v1/chat/completions", body,
                                 timeout=min(300.0, max(remaining(), 1.0)))
    check(status == 200 and doc is not None, f"{where}: HTTP {status}: {raw[:300]!r}")
    check(doc.get("object") == "chat.completion" and str(doc.get("id", "")).startswith("chatcmpl-"),
          f"{where}: not a chat.completion: {raw[:300]!r}")
    choice = doc["choices"][0]
    check(isinstance(choice["message"].get("content"), str), f"{where}: content is not text")
    check_usage(doc.get("usage"), choice.get("finish_reason"), body["max_tokens"], where)
    logprobs = [e["logprob"] for e in (choice.get("logprobs") or {}).get("content", [])]
    return {"text": choice["message"]["content"], "logprobs": logprobs, "usage": doc["usage"],
            "finish_reason": choice["finish_reason"]}


def stream(port: int, body: dict, where: str) -> dict:
    """One streamed completion: SSE framing, one completion id, one finish_reason,
    usage against max_tokens, ``data: [DONE]`` last."""
    body = {**body, "stream": True, "stream_options": {"include_usage": True}}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=min(300.0, max(remaining(), 1.0)))
    try:
        conn.request("POST", "/v1/chat/completions", body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    check(resp.status == 200, f"{where}: HTTP {resp.status}: {raw[:300]!r}")
    check("text/event-stream" in (resp.getheader("Content-Type") or ""),
          f"{where}: Content-Type {resp.getheader('Content-Type')!r}")
    check(raw.endswith(b"\n\n"), f"{where}: stream does not end on an event boundary")
    events = raw.decode().split("\n\n")[:-1]
    check(all(e.startswith("data: ") and "\n" not in e for e in events),
          f"{where}: an event is not one 'data: ' line")
    check(events[-1] == "data: [DONE]", f"{where}: last event is {events[-1][:80]!r}")
    chunks = [json.loads(e[len("data: "):]) for e in events[:-1]]
    check(len(chunks) >= 2, f"{where}: {len(chunks)} chunks")
    check(len({c.get("id") for c in chunks}) == 1 and str(chunks[0]["id"]).startswith("chatcmpl-"),
          f"{where}: completion ids {sorted({str(c.get('id')) for c in chunks})}")
    check(all(c.get("object") == "chat.completion.chunk" for c in chunks), f"{where}: chunk object")
    check(chunks[0]["choices"][0]["delta"].get("role") == "assistant", f"{where}: no role preamble")
    finishes = [c["choices"][0]["finish_reason"] for c in chunks
                if c.get("choices") and c["choices"][0].get("finish_reason")]
    check(len(finishes) == 1, f"{where}: finish_reasons {finishes}")
    usages = [c["usage"] for c in chunks if c.get("usage")]
    check(len(usages) == 1, f"{where}: {len(usages)} usage chunks")
    check_usage(usages[0], finishes[0], body["max_tokens"], where)
    text = "".join(c["choices"][0]["delta"].get("content") or "" for c in chunks if c.get("choices"))
    return {"text": text, "usage": usages[0], "finish_reason": finishes[0], "events": len(events)}


def alphabet(n: int, salt: int) -> str:
    """n printable ASCII characters (one byte-tokenizer token each), seeded by salt."""
    words = ["paged", "cache", "kernel", "token", "slot", "mesh", "chunk", "decode", "prefill"]
    out, i = [], salt
    while sum(len(w) + 1 for w in out) < n:
        out.append(words[i % len(words)] + str(i % 7))
        i += 3
    return " ".join(out)[:n]


# ---------------------------------------------------------------------------
# Sidecar / gateway lifecycle and the sidecar's own account
# ---------------------------------------------------------------------------
def start_sidecar(cfg: dict, name: str, extra_args: list[str], extra_env: dict[str, str]):
    port = free_port()
    argv = [sys.executable, "-m", "inference_gateway_tpu.serving", "--model", cfg["model"],
            "--host", "127.0.0.1", "--port", str(port), "--platform", cfg["platform"],
            *cfg["sidecar_args"], *extra_args]
    proc = spawn(name, argv, {**cfg["env"], **extra_env})
    healthy_after = wait_healthy(name, proc, port, limit=900.0)
    warm = next((r for r in log_records(name) if r.get("msg") == "engine warm"), None)
    check(warm is not None, f"{name}: no 'engine warm' log line")
    check(warm.get("compile_cache") == expected_cache_dir(),
          f"{name} caches compiles in {warm.get('compile_cache')!r}, not {expected_cache_dir()!r}")
    say(phase=name, event="sidecar healthy", seconds_to_healthy=round(healthy_after, 1),
        warmup_seconds=warm["compile_seconds"], compile_cache=warm.get("compile_cache"),
        compile_cache_entries=cache_entries(warm.get("compile_cache")),
        accelerator=warm.get("accelerator"),
        attention_path=warm.get("attention_path"))
    return proc, port, warm


def start_gateway(sidecar_port: int):
    port = free_port()
    proc = spawn("gateway", [sys.executable, "-m", "inference_gateway_tpu.main"], {
        "TPU_API_URL": f"http://127.0.0.1:{sidecar_port}/v1", "SERVER_HOST": "127.0.0.1",
        "SERVER_PORT": str(port)})
    seconds = wait_healthy("gateway", proc, port, limit=60.0)
    listening = next((r for r in log_records("gateway") if r.get("msg") == "gateway listening"), {})
    say(phase="serve", event="gateway healthy", seconds=round(seconds, 1),
        framing=listening.get("framing"))
    return proc, port


def sidecar_account(port: int, cfg: dict, want_path: str, want_devices: int) -> dict:
    """Read what the process that holds the chip says of itself, and hold it to it."""
    status, doc, raw = http_json(port, "GET", "/debug/status")
    check(status == 200 and doc is not None, f"/debug/status: HTTP {status}: {raw[:200]!r}")
    acc = doc.get("accelerator") or {}
    check(acc.get("platform") == cfg["platform"],
          f"sidecar runs on {acc.get('platform')!r}, not {cfg['platform']!r}")
    check(acc.get("device_count") == want_devices,
          f"sidecar holds {acc.get('device_count')} devices, not {want_devices}")
    path = doc.get("attention_path") or {}
    check(path.get("path") == want_path and path.get("mixed_step") is True,
          f"attention_path {path} (want {want_path!r} with the mixed step on)")
    check(doc.get("engine_restarts") == 0, f"engine restarted {doc.get('engine_restarts')} times")
    watchdog = doc.get("engine_watchdog") or {}
    check(watchdog.get("trips") == 0, f"engine watchdog: {watchdog}")
    ledger = (doc.get("device") or {}).get("compile") or {}
    check(ledger.get("warmed") is True and ledger.get("recompiles") == 0,
          f"compiles after warmup: {ledger.get('recompile_events')}")
    hbm = (doc.get("device") or {}).get("hbm") or {}
    if cfg["platform"] == "tpu":
        check(hbm.get("measured") is True, f"HBM not measured on a TPU: {hbm}")
    say(phase="account", accelerator=acc, attention_path=path, engine_restarts=doc["engine_restarts"],
        watchdog_trips=watchdog.get("trips"), compiles=ledger.get("compiles"),
        recompiles_after_warmup=ledger.get("recompiles"),
        programs=sorted({r["program"] for r in ledger.get("records", [])}),
        hbm_plan_bytes=(hbm.get("plan") or {}).get("plan_bytes"), hbm_live_bytes=hbm.get("live_bytes"),
        hbm_live_vs_plan=hbm.get("live_vs_plan"), hbm_per_device=hbm.get("per_device"),
        kv_pages=hbm.get("kv_pages"))
    return {"device": {"platform": acc["platform"], "kind": acc["device_kind"],
                       "count": acc["device_count"]}, "hbm": hbm}


# ---------------------------------------------------------------------------
# Phases on one chip
# ---------------------------------------------------------------------------
def phase_kernels(cfg: dict, rehearse: bool) -> dict:
    """One child on the chip: compiled kernels against their pure-JAX twins."""
    t0 = time.monotonic()
    env = {"JAX_PLATFORMS": cfg["platform"]}
    proc = spawn("kernels", [sys.executable, str(ROOT / "chip_smoke.py"), "--child-kernels",
                             *(["--rehearse-cpu"] if rehearse else [])], env)
    try:
        proc.wait(timeout=max(min(600.0, remaining()), 1.0))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("kernels: child timed out") from None
    finally:
        stop(proc)
    report = next((r for r in reversed(log_records("kernels")) if r.get("phase") == "kernels"), None)
    check(proc.returncode == 0 and report is not None,
          f"kernels: child exited {proc.returncode}: {log_tail('kernels', 8)}")
    say(**report, seconds=round(time.monotonic() - t0, 1))
    check(report["ok"], f"kernels: {[c for c in report['cases'] if not c['ok']]}")
    return report["device"]


def phase_serve(cfg: dict) -> tuple[dict, dict, dict]:
    t0 = time.monotonic()
    sidecar, sport, first = start_sidecar(cfg, "sidecar", [], {})
    gateway, gport = start_gateway(sport)
    status, models, _ = http_json(gport, "GET", "/v1/models?provider=tpu")
    check(status == 200 and any(m.get("id") == f"tpu/{cfg['model']}" for m in models.get("data", [])),
          f"gateway does not list tpu/{cfg['model']}: {models}")

    tokens = 0
    short = complete(gport, chat_body(cfg, "Hi", 8), "non-streamed, short")  # < smallest bucket (16)
    tokens += short["usage"]["completion_tokens"]
    say(phase="serve", request="non-streamed short", usage=short["usage"],
        finish_reason=short["finish_reason"])

    # Several streams at once — decode batches more than one slot — with the
    # long prompt among them, so its chunked prefill shares steps with decode.
    prompts = [(alphabet(40, 1), 24), (alphabet(200, 2), 32),
               (alphabet(cfg["long_prompt_chars"], 3), 40), ("Hi", 48)]
    results: list = [None] * len(prompts)

    def one(i: int, prompt: str, max_tokens: int) -> None:
        try:
            results[i] = stream(gport, chat_body(cfg, prompt, max_tokens), f"stream {i}")
        except Exception as e:  # re-raised on the main thread below
            results[i] = e

    threads = [threading.Thread(target=one, args=(i, p, m)) for i, (p, m) in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(remaining(), 1.0))
    for i, r in enumerate(results):
        if isinstance(r, Exception):
            raise r
        check(r is not None, f"stream {i}: no result before the deadline")
        tokens += r["usage"]["completion_tokens"]
        say(phase="serve", request=f"stream {i}", usage=r["usage"], finish_reason=r["finish_reason"],
            events=r["events"])
    check(results[2]["usage"]["prompt_tokens"] > cfg["long_prompt_chars"],
          "the long prompt did not reach the engine whole")

    # Identical temperature-0 requests, one after the other: the same program on
    # the same inputs. The byte tokenizer drops most of a 32k vocabulary, so the
    # per-token logprobs are the fingerprint that the text alone is not.
    pair_body = chat_body(cfg, alphabet(60, 5), 24, logprobs=True)
    a = complete(gport, pair_body, "greedy pair, first")
    b = complete(gport, pair_body, "greedy pair, second")
    tokens += a["usage"]["completion_tokens"] + b["usage"]["completion_tokens"]
    check(a["text"] == b["text"] and a["logprobs"] == b["logprobs"] and len(a["logprobs"]) > 0,
          f"identical temperature-0 requests differ: {a} vs {b}")
    say(phase="serve", request="greedy pair", identical=True, tokens=len(a["logprobs"]),
        first_logprobs=a["logprobs"][:4])

    account = sidecar_account(sport, cfg, "kernel" if cfg["platform"] == "tpu" else "gather", 1)
    if cfg["platform"] == "tpu":
        ratio = account["hbm"].get("live_vs_plan") or 0.0
        check(0.9 <= ratio <= 1.25, f"HBM in use is {ratio} of hbm_plan, outside 0.9..1.25")
    stop(gateway)
    stop(sidecar)
    say(phase="serve", ok=True, tokens_returned=tokens, seconds=round(time.monotonic() - t0, 1))
    return account["device"], {"body": pair_body, "answer": a}, first


def phase_restart(cfg: dict, first: dict, pair: dict) -> None:
    """The sidecar again, from the compile cache the first start filled.
    ``first`` is the first start's "engine warm" log record."""
    t0 = time.monotonic()
    before = cache_entries(first.get("compile_cache"))
    check(before > 0, f"the compile cache {first.get('compile_cache')!r} is empty after the first start")
    sidecar, sport, warm = start_sidecar(cfg, "sidecar-restart", [], {})
    after = cache_entries(warm.get("compile_cache"))
    again = complete(sport, {**pair["body"], "model": cfg["model"]}, "greedy request after restart")
    check(again["logprobs"] == pair["answer"]["logprobs"] and again["text"] == pair["answer"]["text"],
          "the restarted sidecar answers the temperature-0 request differently")
    stop(sidecar)
    say(phase="restart", ok=True, warmup_seconds_first_start=first["compile_seconds"],
        warmup_seconds_restart=warm["compile_seconds"],
        restart_is_faster=warm["compile_seconds"] < first["compile_seconds"],
        cache_entries_before=before, cache_entries_added_by_restart=after - before,
        same_answer_after_restart=True, seconds=round(time.monotonic() - t0, 1))


def run_one_chip(cfg: dict, rehearse: bool) -> dict:
    kernel_device = phase_kernels(cfg, rehearse)
    device, pair, first = phase_serve(cfg)
    check(device == kernel_device, f"kernels ran on {kernel_device}, the sidecar on {device}")
    phase_restart(cfg, first, pair)
    return device


# ---------------------------------------------------------------------------
# Four chips: tp = 4 through the sidecar CLI, against one device, same weights
# ---------------------------------------------------------------------------
def run_four_chips(cfg: dict, rehearse: bool) -> dict:
    n = 4
    env = {}
    if rehearse:
        # Four virtual CPU devices, and the kernels forced on (interpreted) so
        # the shard_map launch is what the rehearsal walks through.
        env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n}", "IG_TPU_PAGED_KERNEL": "1"}
    bodies = [chat_body(cfg, alphabet(chars, 10 + i), 16, logprobs=True)
              for i, chars in enumerate((8, 60, 200, cfg["long_prompt_chars"]))]

    def ask_all(port: int, where: str) -> list[dict]:
        return [complete(port, {**b, "model": cfg["model"]}, f"{where}, request {i}")
                for i, b in enumerate(bodies)]

    sidecar, port, _ = start_sidecar(cfg, "sidecar-tp4", [], env)
    sharded = ask_all(port, "tp=4")
    want_path = "kernel_sharded"
    account = sidecar_account(port, cfg, want_path, n)
    per_device = account["hbm"].get("per_device") or []
    if cfg["platform"] == "tpu":
        plan = account["hbm"]["plan"]["plan_bytes"]
        check(len(per_device) == n, f"memory_stats for {len(per_device)} devices, not {n}")
        for d in per_device:
            share = d["live_bytes"] / plan
            check(0.2 <= share <= 0.35, f"device {d['id']} holds {share:.3f} of the plan, not about a quarter")
    stop(sidecar)

    single, port, _ = start_sidecar(cfg, "sidecar-one-device", ["--no-mesh"], env)
    alone = ask_all(port, "one device")
    status, doc, _ = http_json(port, "GET", "/debug/status")
    check(status == 200 and doc["attention_path"]["path"] == "kernel", f"one device: {doc.get('attention_path')}")
    stop(single)

    rows = []
    for i, (s, a) in enumerate(zip(sharded, alone)):
        common = 0
        for x, y in zip(s["logprobs"], a["logprobs"]):
            if abs(x - y) > TP_LOGPROB_TOL:
                break
            common += 1
        rows.append({"request": i, "prompt_tokens": s["usage"]["prompt_tokens"],
                     "first_logprob_tp4": s["logprobs"][0], "first_logprob_one": a["logprobs"][0],
                     "tokens_agreeing_within_tol": common, "of": len(a["logprobs"]),
                     "same_text": s["text"] == a["text"]})
    say(phase="compare", tolerance=TP_LOGPROB_TOL, rows=rows)
    for row in rows:
        check(abs(row["first_logprob_tp4"] - row["first_logprob_one"]) <= TP_LOGPROB_TOL,
              f"tp=4 and one device disagree on the next-token logprob: {row}")
    return account["device"]


# ---------------------------------------------------------------------------
# The kernels child (the only code here that imports JAX)
# ---------------------------------------------------------------------------
def child_kernels(rehearse: bool) -> int:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from inference_gateway_tpu.ops.attention import causal_prefill_mask, gqa_attend
    from inference_gateway_tpu.ops.flash_attention import flash_prefill_attention
    from inference_gateway_tpu.ops.paged_attention import (
        GLOBAL_WINDOW,
        paged_attention_jax,
        paged_attention_tpu,
        ragged_paged_attention_jax,
        ragged_paged_attention_tpu,
    )
    from inference_gateway_tpu.utils.platform import configure_compile_cache, device_info

    configure_compile_cache()
    dev = device_info()
    device = {"platform": dev["platform"], "kind": dev["device_kind"], "count": dev["device_count"]}
    if not rehearse and dev["platform"] != "tpu":
        print(json.dumps({"phase": "kernels", "ok": False, "device": device, "cases": [],
                          "error": "not on a TPU"}), flush=True)
        return 1
    interpret = dev["platform"] != "tpu"  # only the rehearsal gets here off the chip
    s = (REHEARSAL if rehearse else REAL)["kernel_shapes"]
    Hq, Hkv, D, ps, S, seq, L = s["Hq"], s["Hkv"], s["D"], s["page"], s["slots"], s["seq"], s["layers"]
    window, dtype = s["window"], jnp.dtype(s["dtype"])
    mp = seq // ps
    P = S * mp
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)

    def pool(key):
        # The engine's layer-stacked pool, (L*P, page, Hkv*D); the last layer's
        # pages hold data, as layer L-1's launch sees them.
        live = jax.random.normal(key, (P, ps, Hkv * D), dtype)
        return jnp.concatenate([jnp.zeros(((L - 1) * P, ps, Hkv * D), dtype), live])

    k_pages, v_pages = pool(keys[0]), pool(keys[1])
    table = np.stack([rng.permutation(P)[:mp] for _ in range(S)]).astype(np.int32) + (L - 1) * P
    table = jnp.asarray(table)
    cases = []

    def compare(name, got, want, mask=None):
        t0 = time.monotonic()
        got = np.asarray(jax.block_until_ready(got)).astype(np.float32)
        want = np.asarray(jax.block_until_ready(want)).astype(np.float32)
        if mask is not None:
            got, want = got[mask], want[mask]
        err = np.abs(got - want)
        ok = bool(np.isfinite(got).all() and (err <= KERNEL_ATOL + KERNEL_RTOL * np.abs(want)).all())
        cases.append({"case": name, "ok": ok, "max_abs_err": float(err.max()),
                      "mean_abs_err": float(err.mean()), "elements": int(err.size),
                      "seconds": round(time.monotonic() - t0, 2)})

    # Decode: one query per slot; an idle slot (its row is zeros), page edges, a
    # full context.
    edge = [0, 1, ps - 1, ps, ps + 1, seq - 1, seq]
    decode_twin = jax.jit(paged_attention_jax, static_argnames=("num_kv_heads", "window"))

    def decode_case(name, q, k_pages, v_pages, table, lengths, Hkv, window, operand=False):
        kw = {"window_dyn": jnp.int32(window or GLOBAL_WINDOW)} if operand else {"window": window}
        compare(name,
                paged_attention_tpu(q, k_pages, v_pages, table, jnp.asarray(lengths), Hkv,
                                    interpret=interpret, **kw),
                decode_twin(q, k_pages, v_pages, table, jnp.asarray(lengths), num_kv_heads=Hkv, window=window))

    lengths = np.asarray((edge + list(rng.integers(1, seq + 1, size=S)))[:S], np.int32)
    q = jax.random.normal(keys[2], (S, Hq, D), dtype)
    decode_case("paged_attention_tpu vs paged_attention_jax", q, k_pages, v_pages, table, lengths, Hkv, window)
    for name, (hq, hkv, slots, win, operand) in (REHEARSAL if rehearse else REAL)["decode_layouts"].items():
        lk = jax.random.split(jax.random.fold_in(keys[7], hq * slots), 3)
        pages = [jax.random.normal(key, (slots * mp, ps, hkv * D), dtype) for key in lk[:2]]
        lens = np.asarray((edge + list(rng.integers(1, seq + 1, size=slots)))[:slots], np.int32)
        lens[rng.permutation(slots)[:slots // 4]] = 0  # the slots an open loop leaves empty
        decode_case(f"paged_attention_tpu vs paged_attention_jax, {name}",
                    jax.random.normal(lk[2], (slots, hq, D), dtype), *pages,
                    jnp.asarray(rng.permutation(slots * mp).reshape(slots, mp).astype(np.int32)),
                    lens, hkv, win, operand)

    # Ragged: the packed axis of one mixed step — prefill chunks (fresh, resumed,
    # ending at the context limit) beside decode rows and idle rows.
    T = s["bucket"] + S
    third = s["bucket"] // 3
    q_lens = np.zeros((S,), np.int32)
    kv_lens = np.zeros((S,), np.int32)
    q_lens[:3] = (third, third, third // 2)
    kv_lens[:3] = (third, min(seq, 4 * third + 7), seq)
    decode_rows = slice(3, max(S - 2, 4))  # the last two rows stay idle where there is room
    n_decode = len(q_lens[decode_rows])
    q_lens[decode_rows] = 1
    kv_lens[decode_rows] = (edge[1:] + list(rng.integers(1, seq + 1, size=S)))[:n_decode]
    q_starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    assert int(q_lens.sum()) <= T
    qr = jax.random.normal(keys[3], (T, Hq, D), dtype)
    ragged_args = (qr, k_pages, v_pages, table, jnp.asarray(q_starts), jnp.asarray(q_lens),
                   jnp.asarray(kv_lens))
    compare("ragged_paged_attention_tpu vs ragged_paged_attention_jax",
            ragged_paged_attention_tpu(*ragged_args, Hkv, interpret=interpret, window=window),
            jax.jit(ragged_paged_attention_jax, static_argnames=("num_kv_heads", "window"))(
                *ragged_args, num_kv_heads=Hkv, window=window))

    # Flash prefill against the masked einsum it replaces, at the largest bucket.
    B, Tq = 8, s["bucket"]
    fq = jax.random.normal(keys[4], (B, Tq, Hq, D), dtype)
    fk = jax.random.normal(keys[5], (B, Tq, Hkv, D), dtype)
    fv = jax.random.normal(keys[6], (B, Tq, Hkv, D), dtype)
    flen = np.asarray(([Tq, 1, Tq - 1, Tq // 2] + list(rng.integers(1, Tq + 1, size=B)))[:B], np.int32)
    positions = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32), (B, Tq))

    @functools.partial(jax.jit, static_argnames=("window",))
    def einsum_prefill(q, k, v, lengths, window):
        mask = causal_prefill_mask(positions, lengths)
        mask = mask & (positions[:, None, :] > positions[:, :, None] - window)
        return gqa_attend(q, k, v, mask)

    compare("flash_prefill_attention vs gqa_attend",
            flash_prefill_attention(fq, fk, fv, jnp.asarray(flen), interpret=interpret, window=window),
            einsum_prefill(fq, fk, fv, jnp.asarray(flen), window=window),
            mask=np.arange(Tq)[None, :] < flen[:, None])

    ok = all(c["ok"] for c in cases)
    print(json.dumps({"phase": "kernels", "ok": ok, "device": device, "interpret": interpret,
                      "tolerance": {"atol": KERNEL_ATOL, "rtol": KERNEL_RTOL},
                      "shapes": s, "cases": cases}), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4 = only the tensor-parallel path and what it is compared with")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny model on the CPU, to rehearse this script's control flow; "
                         "the last line then names the cpu, never tpu")
    ap.add_argument("--child-kernels", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child_kernels:
        return child_kernels(args.rehearse_cpu)

    cfg = REHEARSAL if args.rehearse_cpu else REAL
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    device = None
    failure = None
    try:
        say(phase="start", chips=args.chips, model=cfg["model"], platform=cfg["platform"],
            sidecar_args=cfg["sidecar_args"], rehearsal=args.rehearse_cpu,
            compile_cache=expected_cache_dir(),
            compile_cache_entries_at_start=cache_entries(expected_cache_dir()))
        device = run_four_chips(cfg, args.rehearse_cpu) if args.chips == 4 \
            else run_one_chip(cfg, args.rehearse_cpu)
        check(device["count"] == args.chips, f"{device['count']} devices, asked for {args.chips}")
    except SmokeFailure as e:
        failure = str(e)
    except Exception as e:  # a bug in the smoke is a failed smoke too, and says so
        failure = f"{type(e).__name__}: {e}"
    finally:
        stop_all()
    if failure is not None:
        say(phase="failed", error=failure)
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
