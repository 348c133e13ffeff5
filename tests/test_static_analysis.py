"""graftlint: fixture self-tests per checker + the tier-1 gate (ISSUE 10).

Two layers:

1. **Fixture self-tests** — every checker gets a known-bad snippet that
   MUST flag and a known-good twin that MUST NOT, so a checker that
   silently stops firing (or starts over-firing) fails here before it
   lies about the codebase.
2. **The gate** — the full suite runs over ``inference_gateway_tpu``
   with the committed baseline and asserts zero non-baselined
   violations; a companion test pins the acceptance criterion that the
   baseline holds NO entries for ``resilience/`` or ``serving/`` (those
   were fixed, not grandfathered).

Plus the regression test for the real bug the suite found: the sidecar's
post-hoc span materialization lost the root span (and its trace) when a
child-span build raised mid-loop.
"""

from __future__ import annotations

import json
import sys
import textwrap
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from graftlint import baseline as baseline_mod  # noqa: E402
from graftlint import run_paths, run_source  # noqa: E402

BASELINE_PATH = REPO_ROOT / "graftlint-baseline.json"


def lint(src: str, path: str = "fixture.py", select: str | None = None):
    ids = {select} if select else None
    return run_source(textwrap.dedent(src), path=path, select=ids)


def checker_ids(findings):
    return [f.checker for f in findings]


# ----------------------------------------------------------------------
# async-blocking
# ----------------------------------------------------------------------

def test_async_blocking_flags_sleep_in_async_def():
    bad = """
    import time

    async def handler(req):
        time.sleep(0.5)
        return req
    """
    assert "async-blocking" in checker_ids(lint(bad, select="async-blocking"))


def test_async_blocking_good_twin_awaits_the_clock():
    good = """
    async def handler(req, clock):
        await clock.sleep(0.5)
        return req
    """
    assert lint(good, select="async-blocking") == []


def test_async_blocking_flags_transitive_module_local_call():
    bad = """
    import time

    def warm_cache():
        time.sleep(1.0)

    async def handler(req):
        warm_cache()
        return req
    """
    findings = lint(bad, select="async-blocking")
    assert len(findings) == 1 and "warm_cache" in findings[0].message


def test_async_blocking_sync_only_helper_not_flagged():
    good = """
    import time

    def warm_cache():
        time.sleep(1.0)

    def main():
        warm_cache()
    """
    assert lint(good, select="async-blocking") == []


def test_async_blocking_flags_unbounded_queue_get_and_future_result():
    bad = """
    async def pump(q, fut):
        item = q.get()
        value = fut.result()
        return item, value
    """
    assert len(lint(bad, select="async-blocking")) == 2


def test_async_blocking_allows_awaited_get_and_done_guarded_result():
    good = """
    import asyncio

    async def pump(q, task):
        item = await q.get()
        batch = await asyncio.wait_for(q.get(), 0.1)
        if task.done():
            value = task.result()
        return item, batch
    """
    assert lint(good, select="async-blocking") == []


# ----------------------------------------------------------------------
# clock-discipline
# ----------------------------------------------------------------------

def test_clock_discipline_flags_direct_time_calls():
    bad = """
    import time

    def cooldown_over(opened_at, cooldown):
        return time.monotonic() - opened_at >= cooldown
    """
    assert "clock-discipline" in checker_ids(lint(bad, select="clock-discipline"))


def test_clock_discipline_good_twin_uses_injected_clock():
    good = """
    import time

    def cooldown_over(clock, opened_at, cooldown):
        return clock.now() - opened_at >= cooldown

    def epoch_stamp():
        return time.time_ns()  # epoch stamps via time_ns are fine

    def profile_stamp():
        return time.perf_counter()
    """
    assert lint(good, select="clock-discipline") == []


def test_clock_discipline_catches_from_import_aliases():
    bad = """
    from time import monotonic as mono

    def now():
        return mono()
    """
    assert len(lint(bad, select="clock-discipline")) == 1


def test_clock_discipline_respects_allowlist_and_pragma():
    src = """
    import time

    def now():
        return time.monotonic()
    """
    allowed = lint(src, path="inference_gateway_tpu/resilience/clock.py",
                   select="clock-discipline")
    assert allowed == []
    pragma = """
    import time

    def epoch():
        return time.time()  # graftlint: disable=clock-discipline
    """
    assert lint(pragma, select="clock-discipline") == []


# ----------------------------------------------------------------------
# resource-release
# ----------------------------------------------------------------------

def test_resource_release_flags_happy_path_only_ticket():
    bad = """
    async def middleware(overload, nxt, req):
        ticket = await overload.admit("streaming", 1)
        resp = await nxt(req)
        ticket.release()
        return resp
    """
    findings = lint(bad, select="resource-release")
    assert len(findings) == 1 and "happy path" in findings[0].message


def test_resource_release_good_twin_releases_in_finally():
    good = """
    async def middleware(overload, nxt, req):
        ticket = await overload.admit("streaming", 1)
        try:
            return await nxt(req)
        finally:
            ticket.release()
    """
    assert lint(good, select="resource-release") == []


def test_resource_release_flags_never_released_breaker_slot():
    bad = """
    def attempt(breaker, call):
        ok, took_slot = breaker.admit()
        if not ok:
            return None
        return call()
    """
    findings = lint(bad, select="resource-release")
    assert len(findings) == 1 and "probe slot" in findings[0].message


def test_resource_release_good_twin_settles_breaker_outcome():
    good = """
    def attempt(breaker, call):
        ok, took_slot = breaker.admit()
        if not ok:
            return None
        try:
            result = call()
            breaker.record_success()
            return result
        except Exception:
            breaker.record_failure()
            raise
        finally:
            if took_slot:
                breaker.release()
    """
    assert lint(good, select="resource-release") == []


def test_resource_release_flags_span_without_exception_coverage():
    bad = """
    def traced(tracer, compute):
        span = tracer.start_span("op")
        result = compute()
        tracer.end_span(span)
        return result
    """
    findings = lint(bad, select="resource-release")
    assert len(findings) == 1 and "span" in findings[0].message


def test_resource_release_good_twin_ends_span_in_finally():
    good = """
    def traced(tracer, compute):
        span = tracer.start_span("op")
        try:
            return compute()
        finally:
            tracer.end_span(span)
    """
    assert lint(good, select="resource-release") == []


def test_resource_release_unrelated_with_is_not_coverage():
    """A release wrapped in `with self._lock:` is NOT exception-path
    coverage — the raise that matters happens outside that block
    (code-review finding); only `with <resource>:` itself counts."""
    bad = """
    def traced(self, tracer, compute):
        span = tracer.start_span("op")
        result = compute()
        with self._lock:
            tracer.end_span(span)
        return result
    """
    findings = lint(bad, select="resource-release")
    assert len(findings) == 1 and "happy path" in findings[0].message


def test_resource_release_ownership_transfer_is_not_a_leak():
    good = """
    def open_span(tracer):
        return tracer.start_span("op")  # caller owns it now

    def stash_span(self, tracer):
        self.span = tracer.start_span("op")  # stored: finalized elsewhere
    """
    assert lint(good, select="resource-release") == []


# ----------------------------------------------------------------------
# cross-thread-state
# ----------------------------------------------------------------------

_XTS_TEMPLATE = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self.run, daemon=True)
        self.count = 0

    def run(self):
        {thread_write}

    def reset(self):
        {other_write}
"""


def test_cross_thread_state_flags_unlocked_dual_writes():
    bad = _XTS_TEMPLATE.format(thread_write="self.count += 1",
                               other_write="self.count = 0")
    findings = lint(bad, select="cross-thread-state")
    assert len(findings) == 2  # both unlocked write sites
    assert all("Worker.count" in f.message for f in findings)


def test_cross_thread_state_good_twin_holds_the_lock():
    good = _XTS_TEMPLATE.format(
        thread_write="with self._lock:\n            self.count += 1",
        other_write="with self._lock:\n            self.count = 0")
    assert lint(good, select="cross-thread-state") == []


def test_cross_thread_state_single_side_mutation_is_fine():
    good = _XTS_TEMPLATE.format(thread_write="self.count += 1",
                                other_write="pass")
    assert lint(good, select="cross-thread-state") == []


# ----------------------------------------------------------------------
# cross-process-state
# ----------------------------------------------------------------------

_XPS_TEMPLATE = """
class Ledger:
    def __init__(self, shared):
        self._shared = shared
        self.admitted = 0
        self.shed = 0

    def admit(self):
        {admit_body}

    def shed_one(self):
        {shed_body}
"""


def test_cross_process_state_flags_unmirrored_counter():
    bad = _XPS_TEMPLATE.format(
        admit_body="self.admitted += 1",
        shed_body="self.shed += 1")
    findings = lint(bad, select="cross-process-state")
    assert len(findings) == 2  # both process-local bumps are invisible to peers
    assert all("slab-bound" in f.message for f in findings)


def test_cross_process_state_good_twin_mirrors_into_slab():
    good = _XPS_TEMPLATE.format(
        admit_body=("self.admitted += 1\n"
                    "        self._shared.add('admitted', 1)"),
        shed_body=("self.shed += 1\n"
                   "        self._shared.add('shed', 1)"))
    assert lint(good, select="cross-process-state") == []


def test_cross_process_state_one_mirror_hop_is_compliant():
    # A method that routes through a self-call which itself touches the
    # slab (the `_mirror` idiom in OverloadController) is compliant.
    good = """
    class Ledger:
        def __init__(self, shared):
            self._shared = shared
            self.admitted = 0

        def _mirror(self, name, delta):
            self._shared.add(name, delta)

        def admit(self):
            self.admitted += 1
            self._mirror("admitted", 1)
    """
    assert lint(good, select="cross-process-state") == []


def test_cross_process_state_ignores_unbound_classes():
    # No slab in __init__ -> plain process-local counters are fine.
    good = """
    class Local:
        def __init__(self):
            self.count = 0

        def hit(self):
            self.count += 1
    """
    assert lint(good, select="cross-process-state") == []


def test_cross_process_state_pragma_suppresses_with_reason():
    ok = _XPS_TEMPLATE.format(
        admit_body=("self.admitted += 1  "
                    "# graftlint: disable=cross-process-state -- "
                    "local-only diagnostic, never merged"),
        shed_body="pass")
    assert lint(ok, select="cross-process-state") == []


# ----------------------------------------------------------------------
# jax-hot-path
# ----------------------------------------------------------------------

def test_jax_hot_path_flags_item_inside_jitted_step():
    bad = """
    import jax
    from functools import partial

    @partial(jax.jit, static_argnames=("self",))
    def _decode_fn(self, params, tokens):
        scale = tokens.max().item()
        return params * scale
    """
    findings = lint(bad, select="jax-hot-path")
    assert len(findings) == 1 and ".item()" in findings[0].message


def test_jax_hot_path_good_twin_stays_on_device():
    good = """
    import jax
    import jax.numpy as jnp
    from functools import partial

    @partial(jax.jit, static_argnames=("self",))
    def _decode_fn(self, params, tokens):
        return params * jnp.max(tokens)
    """
    assert lint(good, select="jax-hot-path") == []


def test_jax_hot_path_flags_sync_in_submit_path_scope():
    bad = """
    import numpy as np

    class Scheduler:
        def _submit_chunk(self, chain):
            handle = self.engine.decode_chunk_submit(chain=chain)
            toks = np.asarray(handle.toks_lp)  # materializes = waits
            return toks
    """
    findings = lint(bad, path="inference_gateway_tpu/serving/scheduler.py",
                    select="jax-hot-path")
    assert len(findings) == 1 and "np.asarray" in findings[0].message


def test_jax_hot_path_fetch_functions_are_designated_sync_points():
    good = """
    import numpy as np

    class Scheduler:
        def _submit_chunk(self, chain):
            return self.engine.decode_chunk_submit(chain=chain)

        def _process_chunk(self, handle):
            return np.asarray(handle.toks_lp)  # fetch side: sync is the point
    """
    assert lint(good, path="inference_gateway_tpu/serving/scheduler.py",
                select="jax-hot-path") == []


def test_jax_hot_path_covers_mixed_descriptor_assembly():
    """ISSUE 12: the ragged descriptor-build path is submit-scope —
    materializing a device value while assembling (start, length, kind)
    rows serializes the mixed step against the previous step's results."""
    bad = """
    import numpy as np

    class Scheduler:
        def _build_mixed_rows(self, pending):
            rows = []
            for slot, st in self._slots.items():
                tok = np.asarray(st.pending_dev)  # materializes = waits
                rows.append((slot, int(tok)))
            return rows
    """
    findings = lint(bad, path="inference_gateway_tpu/serving/scheduler.py",
                    select="jax-hot-path")
    assert len(findings) == 1 and "np.asarray" in findings[0].message

    bad_engine = """
    class Engine:
        def mixed_step_submit(self, rows):
            total = sum(len(r.token_ids) for r in rows)
            scale = self.cache_norm.item()  # host sync in a submit fn
            return total * scale
    """
    findings = lint(bad_engine, path="inference_gateway_tpu/serving/engine.py",
                    select="jax-hot-path")
    assert len(findings) == 1 and ".item()" in findings[0].message

    good = """
    import numpy as np

    class Scheduler:
        def _build_mixed_rows(self, pending):
            rows = []
            for slot, st in self._slots.items():
                rows.append((slot, [st.pending_token], st.pos))
            return rows

    class Engine:
        def mixed_step_fetch(self, handle):
            return np.asarray(handle.toks_lp)  # designated sync point
    """
    assert lint(good, path="inference_gateway_tpu/serving/scheduler.py",
                select="jax-hot-path") == []


def test_jax_hot_path_chain_steady_bans_host_construction_and_loops():
    """ISSUE 14: the host-free chained-submit scope — the whole of
    Engine._chain_submit_locked and every `if chain:` branch of
    decode_chunk_submit — additionally bans np.* host-array
    construction, jnp.asarray uploads, and python loops: a chained
    steady-state submit reads persistent state and dispatches, nothing
    else."""
    bad_fn = """
    import numpy as np

    class Engine:
        def _chain_submit_locked(self, n):
            write_idx = np.full((8, n), 0)  # per-chunk host assembly
            for slot in range(8):           # per-slot loop
                write_idx[slot] = slot
            return self._decode_chunk_fn_paged_ee(write_idx)
    """
    findings = lint(bad_fn, path="inference_gateway_tpu/serving/engine.py",
                    select="jax-hot-path")
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "np.full" in msgs and "python loop" in msgs

    bad_branch = """
    import jax.numpy as jnp

    class Engine:
        def decode_chunk_submit(self, tokens, positions, chain=False):
            if chain:
                pos = jnp.asarray(positions)  # upload on the chained path
                return self._chain_submit_locked(pos)
            return self._fresh_submit(tokens, positions)
    """
    findings = lint(bad_branch, path="inference_gateway_tpu/serving/engine.py",
                    select="jax-hot-path")
    assert len(findings) == 1 and "upload" in findings[0].message

    good = """
    import numpy as np
    import jax.numpy as jnp

    class Engine:
        def _chain_submit_locked(self, n):
            need = self._chain_active & (self._pred_pos + n > self._reserved)
            if need.any():
                self._reserve_chain_horizon(need, n)  # amortized slow path
            self._pred_pos = self._pred_pos + n * self._chain_active
            return self._decode_chunk_fn_paged_ee(self.params, self.cache)

        def _reserve_chain_horizon(self, need, n):
            # Outside the chain-steady scope: loops + uploads are the
            # amortized horizon refresh, not per-chunk work.
            for slot in np.nonzero(need)[0]:
                self._ensure_with_evict(int(slot), int(n))
            self._dev_page_table = jnp.asarray(self.allocator.page_table())

        def decode_chunk_submit(self, tokens, positions, chain=False):
            if chain:
                return self._chain_submit_locked(8)
            seeds = np.zeros((8,))  # fresh path may build host arrays
            return self._fresh_submit(tokens, positions, seeds)
    """
    assert lint(good, path="inference_gateway_tpu/serving/engine.py",
                select="jax-hot-path") == []

    # The scope is path-anchored: another module's decode_chunk_submit
    # look-alike is not in scope.
    assert lint(bad_branch, path="somewhere/else.py", select="jax-hot-path") == []


def test_jax_hot_path_covers_structured_mask_upload_path():
    """ISSUE 13: the grammar mask scatter/upload path is submit-scope —
    materializing a device table while loading a span (or registering a
    slot's bias row) serializes the chunk pipeline against the load.
    Mask ADVANCEMENT lives inside the jitted decode scan, covered by the
    jit scope."""
    bad = """
    import numpy as np

    class StructuredRuntime:
        def acquire(self, session):
            rows = session.compiled.automaton.next_state
            current = np.asarray(self.next_dev)  # materializes = waits
            current[: rows.shape[0]] = rows
            return current
    """
    findings = lint(bad, path="inference_gateway_tpu/structured/runtime.py",
                    select="jax-hot-path")
    assert len(findings) == 1 and "np.asarray" in findings[0].message

    bad_register = """
    class StructuredRuntime:
        def register_slot(self, slot, session, logit_bias):
            checksum = self.bias_dev.sum().item()  # host sync
            return checksum
    """
    findings = lint(bad_register, path="inference_gateway_tpu/structured/runtime.py",
                    select="jax-hot-path")
    assert len(findings) == 1 and ".item()" in findings[0].message

    good = """
    import jax.numpy as jnp
    import numpy as np

    class StructuredRuntime:
        def acquire(self, session):
            rows = session.compiled.automaton.next_state + self._base
            self.next_dev = _scatter_rows(self.next_dev, jnp.asarray(rows),
                                          jnp.int32(self._base))
            return self._base

        def stats(self):
            return {"spans": len(self._spans)}
    """
    assert lint(good, path="inference_gateway_tpu/structured/runtime.py",
                select="jax-hot-path") == []


_PACKED_BAD_UPLOADS = """
import jax.numpy as jnp

class Engine:
    def mixed_step_submit(self, rows):
        tokens, positions, page_table = self._assemble(rows)
        return self._mixed_step_fn(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(page_table), self._rng)
"""

_PACKED_BAD_KEY = """
import jax
import jax.numpy as jnp

class Engine:
    def _fresh_submit_ee_locked(self, tokens, n):
        staged = self._fresh_pack.snapshot()
        rng = jax.random.fold_in(self._rng, self._step_counter)  # a dispatch of its own
        self._dev_carry = self._fresh_unpack_fn(jnp.asarray(staged), rng)
        return self._dispatch_chunk_ee_locked(n)
"""

_PACKED_BAD_NEXT_RNG = """
import jax

class Engine:
    def mixed_step_submit(self, rows):
        staged = self._mixed_pack.snapshot()
        return self._mixed_step_fn(self.params, self.cache, jax.device_put(staged),
                                   self._next_rng())
"""

_PACKED_GOOD = """
import jax
import jax.numpy as jnp

class Engine:
    def mixed_step_submit(self, rows):
        v = self._mixed_pack.begin()
        v["counter"][0] = self._bump_step()
        staged = self._mixed_pack.snapshot()
        return self._mixed_step_fn(self.params, self.cache, jnp.asarray(staged), self._rng)

    def _fresh_submit_ee_locked(self, tokens, n):
        ints, floats = self._pack(tokens)
        self._dev_carry = self._fresh_unpack_fn(jnp.asarray(ints), jax.device_put(floats), self._rng)
        return self._dispatch_chunk_ee_locked(n)

    def _next_rng(self):
        return jax.random.fold_in(self._rng, self._bump_step())  # other paths may

    def prefill_submit(self, prompts):
        a, b, c = self._bucket(prompts)
        return self._prefill_fn(self.params, self.cache, jnp.asarray(a), jnp.asarray(b),
                                jnp.asarray(c), self._next_rng())
"""


@pytest.mark.parametrize("source,needle", [
    (_PACKED_BAD_UPLOADS, "upload #3 'jnp.asarray(...)'"),
    (_PACKED_BAD_KEY, "eager key derivation 'jax.random.fold_in(...)'"),
    (_PACKED_BAD_NEXT_RNG, "eager key derivation 'self._next_rng(...)'"),
], ids=["third-upload", "fold-in", "next-rng"])
def test_jax_hot_path_packed_submit_flags_what_brings_the_dispatches_back(source, needle):
    """ISSUE 25: a host-stepped submit stages one packed buffer (two at
    most) and draws its key inside the program."""
    findings = lint(source, path="inference_gateway_tpu/serving/engine.py", select="jax-hot-path")
    assert len(findings) == 1 and needle in findings[0].message
    assert "packed-submit scope" in findings[0].message


def test_jax_hot_path_packed_submit_allows_two_uploads_and_other_paths():
    assert lint(_PACKED_GOOD, path="inference_gateway_tpu/serving/engine.py",
                select="jax-hot-path") == []
    # the scope is the engine's two submits, not every function of that name
    assert lint(_PACKED_BAD_UPLOADS, path="somewhere/else.py", select="jax-hot-path") == []


# ----------------------------------------------------------------------
# telemetry-noop-drift
# ----------------------------------------------------------------------

def test_telemetry_noop_drift_flags_missing_override():
    bad = """
    class OpenTelemetry:
        def record_token_usage(self, *a):
            self.hist.record(a)

        def set_engine_gauges(self, *a):
            self.gauge.set(a)

    class NoopTelemetry(OpenTelemetry):
        def record_token_usage(self, *a):
            pass
    """
    findings = lint(bad, select="telemetry-noop-drift")
    assert len(findings) == 1 and "set_engine_gauges" in findings[0].message


def test_telemetry_noop_drift_good_twin_overrides_everything():
    good = """
    class OpenTelemetry:
        def record_token_usage(self, *a):
            self.hist.record(a)

        def set_engine_gauges(self, *a):
            self.gauge.set(a)

        def expose_prometheus(self):
            return ""  # not a recorder: no override required

    class NoopTelemetry(OpenTelemetry):
        def record_token_usage(self, *a):
            pass

        def set_engine_gauges(self, *a):
            pass
    """
    assert lint(good, select="telemetry-noop-drift") == []


def test_telemetry_noop_drift_holds_on_the_real_module():
    """The lint-time guard agrees with the runtime drift test in
    tests/test_metric_lint.py (which stays as a self-check)."""
    findings, errors = run_paths(
        ["inference_gateway_tpu/otel/otel.py"], REPO_ROOT,
        select={"telemetry-noop-drift"})
    assert errors == []
    assert findings == [], [f.render() for f in findings]


# ----------------------------------------------------------------------
# pragmas + baseline mechanics
# ----------------------------------------------------------------------

def test_standalone_pragma_line_covers_next_line():
    src = """
    import time

    def f():
        # graftlint: disable=clock-discipline
        return time.monotonic()
    """
    assert lint(src, select="clock-discipline") == []


def test_file_pragma_disables_checker_for_whole_module():
    src = """
    # graftlint: disable-file=clock-discipline
    import time

    def f():
        return time.monotonic()

    def g():
        return time.sleep(1)
    """
    assert lint(src, select="clock-discipline") == []


def test_baseline_absorbs_known_findings_and_reports_stale(tmp_path):
    bad = """
    import time

    def f():
        return time.monotonic()
    """
    findings = lint(bad, select="clock-discipline")
    assert findings
    path = tmp_path / "baseline.json"
    baseline_mod.save(path, findings)
    result = baseline_mod.apply(findings, baseline_mod.load(path))
    assert result.new == [] and len(result.baselined) == 1 and result.stale == []
    # The same baseline does NOT absorb a different finding…
    other = lint(bad.replace("monotonic", "time"), select="clock-discipline")
    result2 = baseline_mod.apply(other, baseline_mod.load(path))
    assert len(result2.new) == 1
    # …and the unmatched entry is reported stale (burn-down visibility).
    assert len(result2.stale) == 1


# ----------------------------------------------------------------------
# THE GATE: the real package is clean (tier-1)
# ----------------------------------------------------------------------

def test_package_has_zero_nonbaselined_violations():
    """`python -m graftlint inference_gateway_tpu` must exit 0: every
    finding is fixed, pragma'd with a reason, or grandfathered in the
    committed baseline."""
    findings, errors = run_paths(["inference_gateway_tpu"], REPO_ROOT)
    assert errors == []
    base = baseline_mod.load(BASELINE_PATH)
    result = baseline_mod.apply(findings, base)
    assert result.new == [], "new graftlint violations:\n" + "\n".join(
        f.render() for f in result.new)


def test_baseline_is_empty_for_resilience_and_serving():
    """Acceptance criterion: violations in resilience/ and serving/ were
    FIXED, not baselined (and as shipped the whole baseline is empty)."""
    data = json.loads(BASELINE_PATH.read_text())
    for key in data.get("findings", {}):
        assert "inference_gateway_tpu/resilience/" not in key, key
        assert "inference_gateway_tpu/serving/" not in key, key


def test_cli_entrypoint_runs_clean():
    from graftlint.__main__ import main

    assert main(["--list-checkers"]) == 0
    assert main(["inference_gateway_tpu", "--root", str(REPO_ROOT)]) == 0


# ----------------------------------------------------------------------
# Regression: the real bug the suite found (serving/server.py span
# finalization lost the root span when a child-span build raised).
# ----------------------------------------------------------------------

class _FakeTokenizer:
    eos_token_id = 0


class _FakeEngineConfig:
    model = "fake"
    max_slots = 2
    max_seq_len = 64
    max_prefill_batch = 2
    pipeline_depth = 1
    decode_chunk = 1


class _FakeEngine:
    config = _FakeEngineConfig()
    tokenizer = _FakeTokenizer()
    vision_cfg = None
    spec = False
    spec_ngram = False
    metrics: dict = {}
    allocator = None
    prefix_cache = None

    def context_window(self):
        return 64

    def max_prompt_len(self, multimodal=False):
        return self.context_window() - 1

    def kv_utilization(self):
        return 0.0


def test_sidecar_adopts_external_scheduler_clock():
    """The health staleness comparison must read the SAME timebase the
    scheduler stamps last_step_time on — a sidecar given an external
    scheduler adopts its clock (code-review finding: a virtual-clock
    scheduler against a real-clock server would report permanently
    degraded)."""
    from inference_gateway_tpu.resilience.clock import VirtualClock
    from inference_gateway_tpu.serving.scheduler import Scheduler
    from inference_gateway_tpu.serving.server import SidecarServer

    engine = _FakeEngine()
    vclock = VirtualClock()
    sidecar = SidecarServer(engine, scheduler=Scheduler(engine, clock=vclock),
                            served_model_name="fake")
    assert sidecar._clock is vclock


def test_root_span_survives_child_span_failure():
    from inference_gateway_tpu.otel.tracing import Tracer
    from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler
    from inference_gateway_tpu.serving.server import SidecarServer

    class ExplodingTracer(Tracer):
        def start_span(self, name, **kw):
            if name != "tpu_sidecar.chat_completions":
                raise RuntimeError("child span materialization failed")
            return super().start_span(name, **kw)

    tracer = ExplodingTracer("tpu-sidecar", enabled=True)
    engine = _FakeEngine()
    sidecar = SidecarServer(engine, scheduler=Scheduler(engine),
                            served_model_name="fake", tracer=tracer)
    gen = GenRequest(prompt_ids=[1, 2, 3])
    gen.request_id = "req-test"
    gen.phase_ns.update(submit=1_000, admit=2_000, first_token=3_000,
                        finish=4_000)
    meta = {"id": "chatcmpl-x", "model": "fake", "prompt_tokens": 3}
    with pytest.raises(RuntimeError):
        sidecar._finalize_request(gen, meta, None, 2, stream=False,
                                  finish_reason="stop")
    spans = tracer.drain()
    roots = [s for s in spans if s.name == "tpu_sidecar.chat_completions"]
    assert roots and roots[0].end_ns, (
        "root span must be finalized (and exported) even when a child "
        "span build raises — pre-fix it leaked unexported")
