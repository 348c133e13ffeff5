#!/usr/bin/env python3
"""One run of one benchmark cell: gateway -> sidecar -> engine on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <name> --rehearse-cpu     # control flow only, tiny model

The cell is found by name in BENCHMARK.json; its configuration, traffic mix,
load and metric readers are files found by those names (bench/README.md). This
parent is stdlib Python and never imports JAX: the sidecar child holds the chip
during the window, the reference child after it. The last line of stdout is the
result object of the builder's contract; a run that cannot start (no chip, too
few chips, a child that dies) prints no result and exits 1.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

T0 = time.monotonic()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import client  # noqa: E402
import procs  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402
from procs import BenchFailure, check  # noqa: E402

DRAIN_S = 30.0          # a request still open this long after the window closes has failed
TRACE_SECONDS = 3.0     # the profiler's capture, in the middle of the window
HEALTH_LIMIT_S = 900.0  # a first run compiles
QUEUE_PER_CLIENT = 32   # a closed loop's queue: more requests than any window can finish


def say(**fields) -> None:
    """One earlier line of the report (JSON, flushed)."""
    print(json.dumps({"t": round(time.monotonic() - T0, 2), **fields}), flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(kind: str, name: str):
    """bench/<kind>/<name>.py, loaded by path (metric names may hold dots). A
    name with a dotted suffix ("ttft_p50_ms.batch": one quantity that moves
    another end-to-end metric in other cells, so BENCHMARK.json lists it twice)
    that has no file of its own is read by the reader of the name before the dot."""
    base = name.rsplit(".", 1)[0]
    tried = [BENCH / kind / f"{name}.py", BENCH / kind / f"{base}.py", BENCH / "end_to_end" / f"{base}.py"]
    path = next((t for t in tried if t.is_file()), None)
    check(path is not None, f"no reader bench/{kind}/{name}.py for metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    check(cell is not None, f"no workload {workload!r} in BENCHMARK.json")
    cfg = next((c for c in bench["configs"] if c["name"] == cell["config"]), None)
    check(cfg is not None, f"workload {workload!r} names no configuration of BENCHMARK.json")
    return cell, cfg


def metrics_of(bench: dict, section: str, workload: str) -> list[dict]:
    return [m for m in bench[section] if "workloads" not in m or workload in m["workloads"]]


def fill_env(env: dict[str, str]) -> dict[str, str]:
    return {k: (str(procs.free_port()) if v == "{free_port}" else v) for k, v in env.items()}


def scale_mix(mix: dict, scale: float) -> dict:
    """The rehearsal's toy context: every length of the mix times ``scale``."""
    out = json.loads(json.dumps(mix))
    for key in ("prompt_tokens", "output_tokens"):
        for field in ("median", "min", "max"):
            if field in out[key]:
                out[key][field] = max(int(out[key][field] * scale), 2)
    out["max_total_tokens"] = int(out.get("max_total_tokens", 2000) * scale)
    out["ramp_s"] = min(out.get("ramp_s", 0), 2)
    return out


# ---------------------------------------------------------------------------
# Requests before the window: warm-up and probes
# ---------------------------------------------------------------------------
def bias_of(config: dict) -> tuple[list[int], int]:
    return list(config["logit_bias"]["ids"]), int(config["logit_bias"]["value"])


def warm_up(gport: int, model: str, mix: dict, config: dict, seed: int) -> dict:
    """The cell's own request shapes, with the bias, through the gateway: the
    shortest, the median and the longest prompt of the mix at once, so the masked
    step programs compile (or load) here and a chunked prompt shares steps with
    decode rows before anything is timed."""
    rng = random.Random(seed ^ 0x5EED)
    dist = mix["prompt_tokens"]
    picks = [int(dist["min"]), int(dist.get("median", (dist["min"] + dist["max"]) / 2)), int(dist["max"])]
    reqs = [traffic.Req(index=i, due_s=0.0, max_tokens=24, seed=rng.randrange(1, 2**31 - 1),
                        messages=({"role": "user", "content": traffic.text(rng, n)},))
            for i, n in enumerate(picks)]
    ids, value = bias_of(config)
    docs = [traffic.body(r, model, mix["sampling"], ids, value) for r in reqs]
    tids = [client.new_trace_id(rng) for _ in reqs]
    t = time.monotonic()
    results = asyncio.run(client.run_open(gport, reqs, docs, tids, t, 0.0, 600.0))
    faults = [f"warm-up {r.index}: {r.error or stats.stream_fault(r, None)}" for r in results
              if r.error or stats.stream_fault(r, None)]
    return {"seconds": time.monotonic() - t, "faults": faults,
            "tokens": sum(r.content_chars for r in results)}


async def probe(gport: int, model: str, config: dict, content: str) -> dict:
    """One probe of the reference comparison: not streamed, temperature 0,
    logprobs, the bias. A probe that is refused or malformed is a fault of the
    run, carried in the record and never raised: the window goes on."""
    ids, value = bias_of(config)
    messages = [{"role": "user", "content": content}]
    n = int(config["probes"]["max_tokens"])
    doc = {"model": model, "messages": messages, "max_tokens": n, "temperature": 0,
           "logprobs": True, "logit_bias": {str(i): value for i in ids}}
    got = bytearray()
    rec = {"messages": messages, "asked": n, "fault": None}
    try:
        status, _ = await asyncio.wait_for(client.http_request(
            gport, "POST", "/v1/chat/completions", json.dumps(doc).encode(), {}, got.extend), DRAIN_S)
        out = json.loads(bytes(got)) if status == 200 else None
        if out is None:
            rec["fault"] = f"HTTP {status}: {bytes(got[:200])!r}"
            return rec
        choice = out["choices"][0]
        entries = (choice.get("logprobs") or {}).get("content") or []
        rec.update(text=choice["message"]["content"], logprobs=[e["logprob"] for e in entries],
                   prompt_tokens=out["usage"]["prompt_tokens"],
                   completion_tokens=out["usage"]["completion_tokens"])
        if rec["completion_tokens"] != n or len(rec["logprobs"]) != n or len(rec["text"]) != n:
            rec["fault"] = (f"{rec['completion_tokens']} tokens, {len(rec['logprobs'])} logprobs, "
                            f"{len(rec['text'])} characters for {n} asked")
    except Exception as e:  # a lost connection, no answer in DRAIN_S, a reply that is not JSON
        rec["fault"] = f"{type(e).__name__}: {e}"
    return rec


def probes_before(gport: int, model: str, config: dict, chars: list[int], rng: random.Random) -> tuple[list, list]:
    """Each probe sent twice, alone, before the window: identical requests must
    give identical answers. The first of each pair goes to the reference."""
    out, faults = [], []
    for c in chars:
        content = traffic.text(rng, c)
        p = asyncio.run(probe(gport, model, config, content))
        again = asyncio.run(probe(gport, model, config, content))
        if (again.get("text"), again.get("logprobs")) != (p.get("text"), p.get("logprobs")):
            faults.append(f"two identical temperature-0 probes of {c} characters differ")
        out.append({**p, "where": "alone, before the window"})
    return out, faults


async def probes_in_window(gport: int, model: str, config: dict, contents: list[str],
                           t_open: float, seconds: float) -> list[dict]:
    """The other probes, sent into the measured window at fixed moments
    ((i + 1) / (n + 1) of it), so that each is admitted through mixed steps beside
    the traffic's rows and decodes in its batch: what the reference then checks
    is the served path under load, long contexts included."""
    async def one(i: int, content: str) -> dict:
        at = (i + 1) / (len(contents) + 1) * seconds
        await asyncio.sleep(max(t_open + at - time.monotonic(), 0))
        t = time.monotonic()
        p = await probe(gport, model, config, content)
        return {**p, "where": f"in the window at {at:.1f} s", "seconds": time.monotonic() - t}
    return list(await asyncio.gather(*(one(i, c) for i, c in enumerate(contents))))


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------
async def traced_window(sport: int, trace_dir: Path, at_s: float, t_open: float) -> dict:
    """Ask the process that holds the chip for a profiler capture, mid-window."""
    await asyncio.sleep(max(t_open + at_s - time.monotonic(), 0))
    got = bytearray()
    sent = {"epoch": time.time(), "mono": time.monotonic()}
    status, _ = await client.http_request(
        sport, "GET", f"/debug/jax_trace?seconds={TRACE_SECONDS}&dir={trace_dir}", None, {},
        got.extend)
    try:
        doc = json.loads(bytes(got))
    except ValueError:
        doc = {"raw": bytes(got[:200]).decode(errors="replace")}
    return {"status": status, "reply": doc, "sent_epoch": sent["epoch"], "sent_mono": sent["mono"],
            "returned_mono": time.monotonic()}


async def window(gport: int, sport: int, plan: dict, trace_dir: Path | None) -> tuple[list, list, list, dict | None]:
    """Ramp, then the measured window, then the drain. Returns the ramp's
    results, the window's, the probes sent into it, and what the trace call said."""
    t_open = plan["t_open"]
    seconds = plan["seconds"]
    jobs = []
    if plan["loop"] == "open":
        if plan["ramp"]:
            reqs, docs, tids = plan["ramp"]
            jobs.append(client.run_open(gport, reqs, docs, tids, t_open - plan["ramp_s"],
                                        plan["ramp_s"] + seconds, DRAIN_S))
        reqs, docs, tids = plan["main"]
        jobs.append(client.run_open(gport, reqs, docs, tids, t_open, seconds, DRAIN_S))
    else:
        reqs, docs, tids = plan["main"]
        jobs.append(client.run_closed(gport, reqs, docs, tids, plan["clients"],
                                      t_open - plan["ramp_s"], plan["ramp_s"] + seconds, DRAIN_S))
    jobs.append(probes_in_window(gport, plan["model"], plan["config"], plan["probe_contents"], t_open, seconds))
    if trace_dir is not None:
        jobs.append(traced_window(sport, trace_dir, 0.4 * seconds, t_open))
    done = await asyncio.gather(*jobs)
    trace_reply = done.pop() if trace_dir is not None else None
    window_probes = done.pop()
    if plan["loop"] == "open":
        ramp_results = [_clip(r, t_open, seconds) for r in (done[0] if len(done) == 2 else [])]
        main_results = done[-1]
    else:  # the closed loop's ramp is the requests its clients began before the window
        ramp_results = [_clip(r, t_open, seconds) for r in done[0] if r.due_t < t_open]
        main_results = [_clip(r, t_open, seconds) for r in done[0] if r.due_t >= t_open]
    return ramp_results, main_results, window_probes, trace_reply


def _clip(r, t_open: float, seconds: float):
    """A ramp request counted its tokens against the ramp's own, longer window;
    count again against the measured one."""
    r.chars_in_window = sum(n for t, n in r.frames if t_open <= t < t_open + seconds)
    return r


def child_json(argv: list[str], env: dict[str, str], limit: float, log: Path) -> tuple[int, dict | None]:
    """Run a child to its end; its last stdout line is its JSON result."""
    with open(log, "wb") as err:
        try:
            out = subprocess.run(argv, cwd=ROOT, env={**os.environ, **env}, stdout=subprocess.PIPE,
                                 stderr=err, timeout=limit, start_new_session=True)
        except subprocess.TimeoutExpired:
            return 124, None
    lines = out.stdout.decode(errors="replace").strip().splitlines()
    try:
        return out.returncode, json.loads(lines[-1]) if lines else None
    except ValueError:
        return out.returncode, None


def load_cell(workload: str, rehearse: bool) -> dict:
    """Everything a run of this cell reads, found by the names in BENCHMARK.json."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell, cfg_entry = find_cell(bench, workload)
    config = load_json(ROOT / cfg_entry["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    load = load_json(BENCH / "cells" / f"{cell['name']}.json")
    run_cfg = config["rehearsal"] if rehearse else config
    if rehearse:
        mix = scale_mix(mix, config["rehearsal"]["length_scale"])
        load = {**load, "rate_rps": config["rehearsal"]["rate_rps"], "clients": config["rehearsal"]["clients"]}
    return {"bench": bench, "cell": cell, "config_file": ROOT / cfg_entry["file"], "config": config,
            "mix": mix, "load": load, "chips": load_json(BENCH / "chips.json"), "run_cfg": run_cfg,
            "platform": run_cfg["platform"], "model": f"tpu/{run_cfg['preset']}"}


def start_system(children: procs.Children, c: dict, rehearse: bool) -> dict:
    """Sidecar and gateway by their CLIs, both healthy, the sidecar on the
    platform and the number of chips the cell asks for."""
    config, run_cfg = c["config"], c["run_cfg"]
    sidecar, sport = procs.start_sidecar(children, run_cfg["preset"], c["platform"], run_cfg["sidecar_args"],
                                         {**config["sidecar_env"], **run_cfg.get("sidecar_env", {})})
    gateway, gport = procs.start_gateway(children, sport, fill_env(config["gateway_env"]))
    gateway_s = procs.wait_healthy(children, "gateway", gateway, gport, 120.0)
    procs.wait_healthy(children, "sidecar", sidecar, sport, HEALTH_LIMIT_S)
    healthy_s = time.monotonic() - T0
    warm = procs.wait_log_record(children, "sidecar", "engine warm")
    check(warm.get("compile_cache") == procs.compile_cache_dir(),
          f"sidecar caches compiles in {warm.get('compile_cache')!r}, not {procs.compile_cache_dir()!r}")
    status = procs.sidecar_account(sport, c["platform"], c["cell"]["chips"])
    acc = status["accelerator"]
    if not rehearse:
        check(acc["device_kind"] in c["chips"], f"device kind {acc['device_kind']!r} is not in bench/chips.json")
    say(event="healthy", sidecar_s=healthy_s, gateway_s=gateway_s,
        engine_warmup_s=warm.get("compile_seconds"), accelerator=acc)
    return {"sidecar": sidecar, "gateway": gateway, "sport": sport, "gport": gport,
            "healthy_s": healthy_s, "gateway_s": gateway_s, "accelerator": acc}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny preset on the CPU, to rehearse the control flow; prints no metric")
    args = ap.parse_args()
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    rehearse = args.rehearse_cpu
    c = load_cell(args.workload, rehearse)
    bench, cell, config, mix, load = c["bench"], c["cell"], c["config"], c["mix"], c["load"]
    chips, run_cfg, platform, model = c["chips"], c["run_cfg"], c["platform"], c["model"]
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])

    out_dir = ROOT / "chiprun_out" / "bench" / cell["name"] / f"seed{args.seed}-trace{args.trace}"
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    children = procs.Children(out_dir)
    say(event="start", workload=cell["name"], config=config["name"], traffic=cell["traffic"],
        seed=args.seed, seconds=seconds, trace=args.trace, rehearsal=rehearse,
        compile_cache=procs.compile_cache_dir())

    result = None
    try:
        # -- 1. the system under test ------------------------------------
        sysd = start_system(children, c, rehearse)
        sidecar, gateway, sport, gport = sysd["sidecar"], sysd["gateway"], sysd["sport"], sysd["gport"]
        t_healthy, gateway_s, acc = sysd["healthy_s"], sysd["gateway_s"], sysd["accelerator"]

        # -- 2. warm-up and probes ----------------------------------------
        warmed = warm_up(gport, model, mix, config, args.seed)
        t_warm = time.monotonic() - T0
        probe_rng = random.Random(args.seed ^ 0x9A0BE)
        probe_cfg = run_cfg.get("probes") or config["probes"]
        probes, probe_faults = probes_before(gport, model, config, probe_cfg["before_window_chars"], probe_rng)
        t_probes = time.monotonic() - T0
        say(event="warm", warm_up_s=warmed["seconds"], probes_s=t_probes - t_warm,
            faults=warmed["faults"] + probe_faults)

        # -- 3. the schedule, from the seed alone --------------------------
        ids, value = bias_of(config)
        rng = random.Random(args.seed)
        ramp_s = float(mix.get("ramp_s", 0))
        plan = {"loop": mix["loop"], "seconds": seconds, "ramp_s": ramp_s, "ramp": None,
                "model": model, "config": config,
                "probe_contents": [traffic.text(probe_rng, n) for n in probe_cfg["in_window_chars"]]}

        want_prompt: dict[str, int] = {}  # trace id -> prompt tokens the sidecar must count

        def pack(reqs):
            tids = [client.new_trace_id(rng) for _ in reqs]
            for r, tid in zip(reqs, tids):
                want_prompt[tid] = stats.expected_prompt_tokens(r.messages, config["chat_template"])
            return reqs, [traffic.body(r, model, mix["sampling"], ids, value) for r in reqs], tids

        if mix["loop"] == "open":
            main_reqs = traffic.open_loop(mix, load["rate_rps"], seconds, args.seed)
            plan["main"] = pack(main_reqs)
            if ramp_s > 0:
                plan["ramp"] = pack(traffic.open_loop(mix, load["rate_rps"], ramp_s, args.seed + 1, stream="ramp"))
        else:
            plan["clients"] = int(load["clients"])
            plan["main"] = pack(traffic.closed_loop(mix, QUEUE_PER_CLIENT * plan["clients"], args.seed))
        compiles_before = procs.compile_count(sport)
        plan["t_open"] = time.monotonic() + ramp_s + 0.05
        setup_s = plan["t_open"] - T0
        epoch_open = time.time() + (plan["t_open"] - time.monotonic())
        say(event="window opens", setup_s=setup_s, loop=mix["loop"],
            rate_rps=load.get("rate_rps"), clients=plan.get("clients"),
            requests=len(plan["main"][0]) if mix["loop"] == "open" else None, ramp_s=ramp_s)

        # -- 4. the window --------------------------------------------------
        trace_dir = out_dir / "trace" if args.trace else None
        ramp_results, results, window_probes, trace_reply = asyncio.run(window(gport, sport, plan, trace_dir))
        probes += window_probes
        probe_faults += [f"probe {p['where']}: {p['fault']}" for p in probes if p["fault"]]
        t_closed = time.monotonic() - T0

        # -- 5. the sidecar's account ---------------------------------------
        compiles_after = procs.compile_count(sport)
        status1 = procs.sidecar_account(sport, platform, cell["chips"])
        timeline = []
        if args.trace:
            _, tl, _ = procs.http_json(sport, "GET", "/debug/timeline")
            timeline = [t for t in (tl or {}).get("entries", [])
                        if epoch_open <= t.get("ts", 0) <= epoch_open + seconds]
        hbm = (status1.get("device") or {}).get("hbm") or {}
        children.stop(gateway)
        children.stop(sidecar)
        access = {e["trace_id"]: e for e in children.log_records("sidecar")
                  if e.get("log") == "access" and e.get("trace_id")}

        # -- 6. children of our own, now that the chip is free ----------------
        trace = None
        if args.trace:
            say(event="trace call", **{k: v for k, v in (trace_reply or {}).items() if k != "sent_mono"})
            rc, trace = child_json([sys.executable, str(BENCH / "trace_reduce.py"), str(trace_dir),
                                    str(BENCH / "trace_classes.json"), str(run_cfg.get("model", config)["vocab_size"])],
                                   {"JAX_PLATFORMS": "cpu"},
                                   240.0, out_dir / "trace_reduce.log")
            (out_dir / "trace_summary.json").write_text(json.dumps(trace))
            if rc != 0 or not trace or trace.get("error"):
                say(event="trace reduction failed", rc=rc, detail=(trace or {}).get("error"))
                trace = None
            else:
                # The trace's clock starts at 0 with the capture, so the client
                # knows only roughly when it ran: from when it asked for it.
                trace["mono_start"] = trace_reply["sent_mono"]
        t_ref = time.monotonic()
        (out_dir / "probes.json").write_text(json.dumps(
            {"bias_ids": ids, "bias": value, "probes": [p for p in probes if not p["fault"]]}))
        ref_rc, ref = child_json(
            [sys.executable, str(BENCH / "reference.py"), str(c["config_file"]),
             str(out_dir / "probes.json"), *(["--rehearse-cpu"] if rehearse else [])],
            {}, 300.0, out_dir / "reference.log")
        say(event="reference", rc=ref_rc, seconds=time.monotonic() - t_ref, result=ref)

        # -- 7. correct? -------------------------------------------------------
        faults = list(warmed["faults"]) + list(probe_faults)
        everything = ramp_results + results
        for r in everything:
            f = stats.stream_fault(r, want_prompt.get(r.trace_id))
            if f:
                faults.append(f"request {r.index}: {f}")
        failed = [r for r in everything if r.error is not None]
        attempted = len(everything) + len(window_probes)   # the window's probes are requests of the run too
        n_failed = len(failed) + sum(1 for p in window_probes if p["fault"])
        faults += procs.account_faults(status1, platform, run_cfg["attention_path"])
        if compiles_after != compiles_before:
            faults.append(f"{compiles_after - compiles_before} compiles inside the window")
        if ref_rc != 0 or not ref or not ref.get("ok"):
            faults.append(f"reference comparison failed (rc {ref_rc}): largest difference "
                          f"{(ref or {}).get('max_abs_diff')} nats, mean {(ref or {}).get('mean_abs_diff')}")
        if args.trace and not rehearse and trace is None:
            faults.append("no device trace could be read")
        counted = sum(r.content_chars for r in everything if r.error is None)
        billed = sum(r.usages[0]["completion_tokens"] for r in everything
                     if r.error is None and len(r.usages) == 1)
        if counted != billed:
            faults.append(f"content frames carried {counted} tokens, usage says {billed}")

        # -- 8. metrics ----------------------------------------------------------
        sources = {
            "requests": results, "all_requests": everything,
            "window": {"seconds": seconds, "t_open": plan["t_open"], "epoch_open": epoch_open},
            "access_log": access, "timeline": timeline, "trace": trace, "status": status1,
            "config": run_cfg if rehearse else config, "traffic": mix, "cell": {**cell, **load},
            "chip": chips.get(acc["device_kind"], {}),
            "setup": {"setup_s": setup_s, "healthy_s": t_healthy, "gateway_s": gateway_s,
                      "warm_up_s": warmed["seconds"], "probes_s": t_probes - t_warm, "ramp_s": ramp_s},
        }
        section = "per_layer" if args.trace else "end_to_end"
        kind = "layers" if args.trace else "end_to_end"
        metrics = {}
        for m in metrics_of(bench, section, cell["name"]):
            reader = load_reader(kind, m["name"])
            check(reader.UNIT == m["unit"], f"{m['name']}: reader says {reader.UNIT}, BENCHMARK.json {m['unit']}")
            value_ = reader.compute(sources)
            if value_ is not None:
                metrics[m["name"]] = {"value": value_, "unit": m["unit"]}
        ok_reqs = stats.measured(sources)
        late = client.lateness(results)
        say(event="window closed", closed_at=t_closed, attempted=attempted, failed=n_failed,
            in_window=len(results), answered=len(ok_reqs), generator_late=late,
            tokens_in_window=sum(r.chars_in_window for r in everything),
            tokens_per_s_this_run=sum(r.chars_in_window for r in everything) / seconds,  # traced or not
            ttft_samples=sum(1 for r in ok_reqs if r.first_content_t is not None),
            tpot_samples=sum(1 for r in ok_reqs if r.content_chars >= 2),
            compiles_before=compiles_before, compiles_after=compiles_after,
            hbm_live_bytes=hbm.get("live_bytes"), hbm_plan_bytes=(hbm.get("plan") or {}).get("plan_bytes"),
            setup=sources["setup"], faults=faults[:12], n_faults=len(faults))

        device = {"platform": acc["platform"], "kind": acc["device_kind"], "count": acc["device_count"],
                  "memory_peak_bytes": max((d["peak_bytes"] for d in hbm.get("per_device", [])), default=0)}
        result = {"correct": not faults, "attempted": attempted, "failed": n_failed,
                  "metrics": {} if rehearse else metrics, "device": device}
        if args.trace and trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            classes = sorted(trace["class_s"].items(), key=lambda kv: -kv[1])
            ops = [[f"class:{k}", v] for k, v in classes][:5]
            ops += [[f"op:{k[:80]}", v] for k, v in trace["top_ops"]][: 10 - len(ops)]
            result["breakdown"] = {"device_ops": ops,
                                   "idle_gaps": [[k[:120], v] for k, v in trace["idle_by_host"]]}
        if rehearse and args.trace:
            say(event="rehearsal metrics (never a device number)", values=metrics)
        (out_dir / "run.json").write_text(json.dumps({
            "result": result, "faults": faults, "setup": sources["setup"], "generator_late": late,
            "reference": ref, "trace": {k: v for k, v in (trace or {}).items() if k not in ("layout", "host_names")},
            "requests": [{"index": r.index, "ttft_ms": stats.ttft_ms(r), "tpot_ms": stats.tpot_ms(r),
                          "tokens": r.content_chars, "late_ms": r.late_s * 1e3, "error": r.error}
                         for r in results]}, default=str))
    except BenchFailure as e:
        say(event="failed", error=str(e))
        return 1
    finally:
        children.stop_all()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
