"""Seeded property test (ISSUE 1 satellite): 1k randomized fault scripts
driven through a 3-deployment pool. Invariants:

1. **Deadline**: no request's virtual elapsed time ever exceeds its
   deadline budget — retries and failovers re-divide the deadline, they
   never extend it.
2. **No healthy skip**: when a request fails outright (not by deadline),
   every deployment that was healthy (circuit not open) at request start
   was actually attempted — the failover walk never silently skips a
   viable replica.

Pure stdlib ``random.Random(seed)`` (no hypothesis), virtual clock, zero
real sleeps — tier-1 fast.
"""

import random

from inference_gateway_tpu.config import ResilienceConfig
from inference_gateway_tpu.netio.client import HTTPClientError
from inference_gateway_tpu.providers.core import HTTPError
from inference_gateway_tpu.providers.routing import Deployment, Pool
from inference_gateway_tpu.resilience import (
    BudgetExceededError,
    Resilience,
    UpstreamUnavailableError,
    VirtualClock,
)

SEED = 20260803
TRIALS = 1000


def _random_fault(rng: random.Random):
    r = rng.random()
    if r < 0.35:
        return ("ok", 0.0)
    if r < 0.55:
        return ("reset", 0.0)
    if r < 0.70:
        return ("s503", rng.choice([None, round(rng.uniform(0.0, 3.0), 3)]))
    if r < 0.80:
        return ("s429", round(rng.uniform(0.0, 5.0), 3))
    return ("slow", round(rng.uniform(0.5, 40.0), 3))


async def _run_trials() -> None:
    rng = random.Random(SEED)
    successes = failures = deadline_hits = 0
    for trial in range(TRIALS):
        clk = VirtualClock()
        cfg = ResilienceConfig(
            breaker_failure_threshold=rng.choice([1, 2, 3, 5]),
            breaker_cooldown=round(rng.uniform(5.0, 60.0), 3),
            breaker_half_open_probes=1,
            retry_max_attempts=rng.choice([1, 2, 3]),
            retry_base_backoff=0.1,
            retry_max_backoff=2.0,
            request_budget=round(rng.uniform(0.5, 20.0), 3),
        )
        res = Resilience(cfg, clock=clk, rng=random.Random(trial))
        pool = Pool("alias", [Deployment(p, "m") for p in ("a", "b", "c")])

        for _ in range(rng.randint(1, 6)):
            attempted: list[str] = []
            healthy_at_start = {
                d.provider for d in pool.deployments if res.healthy(d)
            }
            budget = res.new_budget()

            async def call(cand, b, rng=rng, attempted=attempted, clk=clk):
                attempted.append(cand.provider)
                kind, arg = _random_fault(rng)
                timeout = b.timeout()  # budget-derived, like the handlers
                if kind == "ok":
                    return cand.provider
                if kind == "reset":
                    raise HTTPClientError("ConnectionResetError (injected)")
                if kind == "s503":
                    raise HTTPError(503, "unavailable", retry_after=arg)
                if kind == "s429":
                    raise HTTPError(429, "throttled", retry_after=arg)
                # slow: upstream stalls for `arg`s; the caller's timeout
                # fires first when smaller — burning that much budget.
                await clk.sleep(min(arg, timeout))
                if arg >= timeout:
                    raise HTTPClientError("TimeoutError (injected slow upstream)")
                return cand.provider

            candidates = pool.candidates(healthy=res.healthy)
            start = clk.now()
            outcome = "ok"
            try:
                await res.execute(candidates, call, budget=budget,
                                  idempotent=True, alias="alias")
                successes += 1
            except BudgetExceededError:
                deadline_hits += 1
                outcome = "deadline"
            except (UpstreamUnavailableError, HTTPError, HTTPClientError):
                failures += 1
                outcome = "failed"
            elapsed = clk.now() - start

            # Invariant 1: the deadline budget is a hard wall.
            assert elapsed <= budget.total + 1e-9, (
                f"trial {trial}: elapsed {elapsed:.3f}s exceeded "
                f"budget {budget.total:.3f}s"
            )
            # Invariant 2: a non-deadline failure means every deployment
            # healthy at request start was attempted.
            if outcome == "failed":
                assert healthy_at_start <= set(attempted), (
                    f"trial {trial}: healthy {sorted(healthy_at_start)} "
                    f"but only attempted {sorted(set(attempted))}"
                )

    # The mix must actually exercise all three outcomes.
    assert successes > 0 and failures > 0 and deadline_hits > 0, (
        successes, failures, deadline_hits)


def test_fuzz_1k_fault_scripts_hold_invariants(aloop):
    aloop.run(_run_trials())


# ---------------------------------------------------------------------------
# Engine-fault fuzz (ISSUE 7): seeded exhaustion/device-error scripts under
# concurrent load. Invariants:
#
# 1. **No token lost or duplicated**: a request that completes (stop/
#    length) delivers a stream byte-identical to its no-fault greedy
#    baseline — preemption resume neither drops nor repeats a token; a
#    request that errors delivered a strict PREFIX of its baseline.
# 2. **Preemption budget**: no request is preempted more than preempt_max
#    times; pressure past the budget degrades to a clean "error", never a
#    hang (every request reaches exactly one terminal callback).
# 3. **No leaks**: slot pool fully restored after every trial.
# ---------------------------------------------------------------------------
ENGINE_TRIALS = 12
PREEMPT_MAX = 2


def test_engine_fault_fuzz_no_token_lost_or_duplicated():
    import queue
    import time

    from inference_gateway_tpu.resilience.faults import EngineFaultInjector
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler

    cfg = EngineConfig(model="test-tiny", max_slots=4, max_seq_len=96,
                       dtype="float32", max_prefill_batch=2, use_mesh=False,
                       attention="dense", decode_chunk=2,
                       prefill_buckets=(16, 32, 64))
    eng = Engine(cfg)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6], [3, 3, 3], [9, 8, 7]]
    max_tokens = [8, 6, 10, 7, 9, 6]

    def run_requests(sched, order):
        results: "queue.Queue[tuple]" = queue.Queue()
        streams: dict[int, list[int]] = {i: [] for i in order}

        def cb_factory(i):
            def cb(tok, lp, fin, reason):
                if not (fin and reason in ("stop", "error")):
                    streams[i].append(tok)
                if fin:
                    results.put((i, reason))
            return cb

        reqs = {}
        for i in order:
            reqs[i] = GenRequest(prompt_ids=list(prompts[i]),
                                 max_tokens=max_tokens[i],
                                 callback=cb_factory(i), request_id=f"f{i}")
            sched.submit(reqs[i])
        got = {}
        for _ in order:
            i, reason = results.get(timeout=120)
            got[i] = (streams[i], reason)
        return got, reqs

    # Baselines: one clean scheduler, no faults, greedy.
    sched = Scheduler(eng)
    sched.start()
    try:
        base, _ = run_requests(sched, list(range(len(prompts))))
    finally:
        sched.stop()
    for i, (toks, reason) in base.items():
        assert reason in ("stop", "length"), (i, reason)

    rng = random.Random(20260803)
    preempted_total = 0
    for trial in range(ENGINE_TRIALS):
        sched = Scheduler(eng, preempt_max=PREEMPT_MAX)
        inj = EngineFaultInjector(eng)
        try:
            for _ in range(rng.randint(1, 4)):
                kind = rng.choice(["exhaust", "exhaust", "error"])
                inj.at("decode_submit", rng.randint(0, 10), kind)
            order = list(range(len(prompts)))
            rng.shuffle(order)
            sched.start()
            got, reqs = run_requests(sched, order)
            for i, (toks, reason) in got.items():
                if reason in ("stop", "length"):
                    assert toks == base[i][0], (
                        f"trial {trial} req {i}: completed stream diverged")
                else:
                    assert reason == "error", (trial, i, reason)
                    assert toks == base[i][0][:len(toks)], (
                        f"trial {trial} req {i}: errored stream is not a "
                        "prefix of its baseline")
                assert reqs[i].preempt_count <= PREEMPT_MAX, (trial, i)
            preempted_total += sched.preemptions
            # Poll the asserted condition itself: a request leaves _slots
            # a moment before its slot re-enters _free, and that window
            # now includes the ISSUE 14 carry-freeze dispatch.
            deadline = time.monotonic() + 10
            while (time.monotonic() < deadline
                   and (sched.active_requests()
                        or len(sched._free) < cfg.max_slots)):
                time.sleep(0.01)
            assert sorted(sched._free) == list(range(cfg.max_slots)), trial
        finally:
            inj.uninstall()
            sched.stop()
    # The mix must actually exercise the preemption machinery.
    assert preempted_total > 0


# ---------------------------------------------------------------------------
# Continuation splice fuzz (ISSUE 9): seeded mid-stream kill scripts
# against a continuation-aware upstream on a VirtualClock. Invariants:
#
# 1. **Splice equality**: with the kill count within
#    RESILIENCE_STREAM_RETRY_MAX, the client stream is byte-identical to
#    the unkilled run — whatever the kill mode (reset, stall, dead
#    pre-first-byte) or its position, including kills landing mid-frame
#    via random block chopping.
# 2. **Once-only billing**: for deterministic kill modes every content
#    frame is generated exactly once across all attempts (resets/deads);
#    client-visible usage always equals the unkilled run's.
# 3. **One trace id** spans every establishment of a trial.
# ---------------------------------------------------------------------------
CONTINUATION_TRIALS = 40


async def _continuation_trials() -> None:
    from tests.test_stream_continuation import (
        TRACEPARENT,
        ContinuationUpstream,
        _drain,
        _make_router,
        _post_chat_stream,
    )
    from inference_gateway_tpu.netio.sse import DONE_FRAME, split_sse_payloads
    import json as _json

    rng = random.Random(20260804)
    for trial in range(CONTINUATION_TRIALS):
        deltas = ["".join(rng.choice("abcdefgh !?") for _ in range(rng.randint(1, 4)))
                  for _ in range(rng.randint(3, 9))]

        clk0 = VirtualClock()
        base_up = ContinuationUpstream(clk0, deltas=deltas,
                                       rng=random.Random(trial))
        router0, _ = _make_router(base_up)
        unkilled = await _drain(await router0.chat_completions_handler(
            _post_chat_stream()))
        assert DONE_FRAME in unkilled, trial

        n_kills = rng.randint(1, 2)  # within stream_retry_max=2
        kills = []
        for _ in range(n_kills):
            mode = rng.choice(["reset", "reset", "stall", "dead"])
            if mode == "dead":
                kills.append(("dead",))
            elif mode == "stall":
                # A stall that relays content is a post-first-byte death
                # (fresh establishment budget). A stall with nothing
                # relayed burns the ORIGINAL budget by design — the
                # client's deadline passed while the upstream said
                # nothing — so the pre-first-byte variant correctly
                # fails and is excluded from the always-recovers fuzz.
                kills.append(("stall", rng.randint(1, len(deltas) - 1)))
            else:
                kills.append(("reset", rng.randint(0, len(deltas) - 1)))
        clk = VirtualClock()
        upstream = ContinuationUpstream(clk, deltas=deltas, kills=list(kills),
                                        rng=random.Random(trial * 7 + 1))
        router, _ = _make_router(upstream, n_candidates=4)
        body = await _drain(await router.chat_completions_handler(
            _post_chat_stream()))

        assert body == unkilled, (trial, kills)
        assert set(upstream.traceparents) == {TRACEPARENT}, trial
        if all(k[0] != "stall" for k in kills):
            # Stall kills may drop an already-yielded block at the idle
            # guard (never relayed NOR observed — self-consistent), so
            # the exactly-once count is asserted for the deterministic
            # modes only; byte-equality above covers stalls. Each reset
            # serves a prefix and its continuation serves exactly the
            # remainder ("dead" serves nothing), so the total is the
            # token count — one generation per token, ever.
            assert upstream.content_served == len(deltas), (trial, kills)
        usage = next((_json.loads(p).get("usage")
                      for p in split_sse_payloads(body)
                      if _json.loads(p).get("usage")), None)
        assert usage and usage["completion_tokens"] == len(deltas), trial


def test_continuation_fuzz_seeded_kill_scripts(aloop):
    aloop.run(_continuation_trials())


# ---------------------------------------------------------------------------
# Desynchronized-decode byte-identity fuzz (ISSUE 14): seeded trials
# mixing injected KV-pressure preemption, continuation splices, and
# stop-token / stop-string-shaped / max_tokens / grammar-end finishes.
# Two full scheduler stacks run the SAME request scripts — one at four
# steps a chunk, where rows freeze mid-chunk and chunks exit early, and
# a reference at one step a chunk, where neither can happen and the
# host decides every stop — and every stream must come out
# byte-identical and once-only billed in every combination (preemption
# and on-device stopping are both transparent).
# ---------------------------------------------------------------------------

DESYNC_SEED = 20260804
DESYNC_TRIALS = 3


def _desync_stack(decode_chunk: int):
    from inference_gateway_tpu.resilience.faults import EngineFaultInjector
    from inference_gateway_tpu.serving.engine import Engine, EngineConfig
    from inference_gateway_tpu.serving.scheduler import Scheduler

    eng = Engine(EngineConfig(
        model="test-tiny", max_slots=4, max_seq_len=128, dtype="float32",
        max_prefill_batch=2, use_mesh=False, attention="paged", page_size=16,
        prefix_cache=False, decode_chunk=decode_chunk, prefill_buckets=(16, 32)))
    sched = Scheduler(eng, preempt_max=5)
    sched.start()
    return eng, sched, EngineFaultInjector(eng)


def _desync_run(sched, script, timeout=240.0):
    import queue as _q

    from inference_gateway_tpu.serving.scheduler import GenRequest

    out = [([], [None]) for _ in script]
    done: _q.Queue = _q.Queue()

    def cb_factory(i):
        def cb(tok, lp, fin, reason):
            if not (fin and reason == "stop"):
                out[i][0].append(tok)
            if fin:
                out[i][1][0] = reason
                done.put(i)
        return cb

    reqs = []
    for i, spec in enumerate(script):
        reqs.append(GenRequest(
            prompt_ids=list(spec["prompt"]), max_tokens=spec["max_tokens"],
            temperature=spec["temp"], top_p=0.9 if spec["temp"] else 1.0,
            seed=spec["seed"], stop_token_ids=frozenset(spec["stops"]),
            grammar=spec["grammar"], callback=cb_factory(i),
            resume_generated=spec.get("resume", 0)))
    for r in reqs:
        sched.submit(r)
    for _ in script:
        done.get(timeout=timeout)
    return [(toks, r[0]) for toks, r in out]


def test_desync_decode_fuzz_byte_identity_and_once_only_billing():
    rng = random.Random(DESYNC_SEED)
    eng_on, s_on, inj_on = _desync_stack(4)
    eng_ref, s_ref, inj_ref = _desync_stack(1)
    try:
        seen_tokens: list = []
        for trial in range(DESYNC_TRIALS):
            script = []
            n_reqs = rng.randint(3, 4)
            for i in range(n_reqs):
                prompt = [rng.randint(1, 40) for _ in range(rng.randint(2, 6))]
                temp = rng.choice([0.0, 0.0, 0.7])
                spec = {
                    "prompt": prompt,
                    "max_tokens": rng.randint(1, 18),
                    "temp": temp,
                    "seed": rng.randint(1, 10_000) if temp else None,
                    "stops": set(),
                    "grammar": None,
                }
                # Stop sets drawn from tokens earlier trials actually
                # emitted, so stop-token finishes really fire; an
                # occasional oversized set exercises the host backstop
                # past the device table width.
                if seen_tokens and rng.random() < 0.5:
                    spec["stops"] = {rng.choice(seen_tokens)
                                     for _ in range(rng.randint(1, 3))}
                    if rng.random() < 0.3:
                        spec["stops"] |= set(range(3000, 3012))
                script.append(spec)
            if trial % 2 == 1:
                # One grammar-constrained request per odd trial — each
                # stack gets its OWN session (host-mirror state).
                script[0]["stops"] = set()
                script[0]["temp"], script[0]["seed"] = 0.0, None
                script[0]["max_tokens"] = rng.randint(8, 40)
                g_on = eng_on.structured.session_for({"type": "json_object"})
                g_ref = eng_ref.structured.session_for({"type": "json_object"})
            # Inject 0-2 recoverable page exhaustions at a shared future
            # call index: whatever preemption each stack actually
            # performs, streams must stay identical.
            for _ in range(rng.randint(0, 2)):
                off = rng.randint(1, 6)
                inj_on.at("decode_submit",
                          inj_on.calls["decode_submit"] + off, "exhaust")
                inj_ref.at("decode_submit",
                           inj_ref.calls["decode_submit"] + off, "exhaust")
            script_on = [dict(s) for s in script]
            script_ref = [dict(s) for s in script]
            if trial % 2 == 1:
                script_on[0]["grammar"] = g_on
                script_ref[0]["grammar"] = g_ref
            got_on = _desync_run(s_on, script_on)
            got_ref = _desync_run(s_ref, script_ref)
            assert got_on == got_ref, (trial, got_on, got_ref)
            for (toks, reason), spec in zip(got_on, script):
                # Once-only billing: never more than max_tokens emitted,
                # across any preemption resume.
                assert len(toks) <= spec["max_tokens"], (trial, spec, toks)
                assert reason in ("stop", "length"), (trial, reason)
                seen_tokens.extend(t for t in toks[2:] if t > 0)
            # Continuation splice (greedy, unconstrained, length-finished
            # streams): resume from prompt + emitted-so-far with the
            # remaining budget — the spliced stream must extend the
            # original byte-identically on BOTH stacks.
            constrained_idx = 0 if trial % 2 == 1 else None
            pick = next((i for i, sp in enumerate(script)
                         if i != constrained_idx and sp["temp"] == 0.0
                         and got_on[i][1] == "length" and got_on[i][0]), None)
            if pick is None:
                continue
            head_spec = script[pick]
            head_toks, _head_reason = got_on[pick]
            extended = {**head_spec, "grammar": None,
                        "max_tokens": head_spec["max_tokens"] + 5}
            splice = {**extended,
                      "prompt": list(head_spec["prompt"]) + head_toks,
                      "resume": len(head_toks)}
            ref_on = _desync_run(s_on, [dict(extended)])
            spl_on = _desync_run(s_on, [dict(splice)])
            spl_ref = _desync_run(s_ref, [dict(splice)])
            assert head_toks + spl_on[0][0] == ref_on[0][0], trial
            assert spl_on[0] == spl_ref[0], trial
    finally:
        inj_on.uninstall()
        inj_ref.uninstall()
        s_on.stop()
        s_ref.stop()
