"""jax-hot-path: no host synchronization in the engine step path.

ROADMAP item 2 (desynchronized decode) is about removing host↔device
round-trips from the steady-state serving loop; this checker keeps new
ones from creeping in — "compile-time elimination of synchronization
mistakes" (Kernel Looping, arxiv 2410.23668) applied to the host side.

Two scopes:

1. **Jitted step functions** — any function decorated with ``jax.jit``
   / ``partial(jax.jit, ...)``: host syncs (``.item()``,
   ``.block_until_ready()``, ``jax.device_get``, ``np.asarray``,
   ``float()``/``int()`` on expressions) are trace-time errors or
   silent constant-folding hazards; all are flagged.

2. **Submit-path functions** (named, host-side): the functions whose
   contract is "dispatch without waiting" — ``Engine.decode_chunk_submit``
   / ``Engine._scatter_admission`` / ``Engine.mixed_step_submit`` and
   ``Scheduler._submit_chunk`` / ``Scheduler.run`` and its ``_pass`` /
   ``Scheduler._process_handles`` / ``Scheduler._build_mixed_rows``
   (the ISSUE 12 ragged descriptor assembly: building the per-row
   (start, length, kind) arrays must stay pure host bookkeeping — a
   sync there serializes the mixed step against the previous step's
   results). There, only the genuine sync primitives are banned:
   ``.item()``, ``.block_until_ready()``, ``jax.device_get``, and
   ``np.asarray`` / ``np.array`` **on anything** — a submit function
   that materializes a device value serializes the pipeline it exists
   to overlap. (Fetch functions — ``decode_chunk_fetch``,
   ``prefill_fetch``, ``mixed_step_fetch`` — are the designated sync
   points and are not in scope.)

3. **Chain-steady scope** (ISSUE 14): the host-free chained-decode
   steady state — ``Engine._chain_submit_locked`` whole, plus every
   ``if chain:`` branch inside ``decode_chunk_submit``. A chained
   submit must upload NOTHING and assemble NOTHING, so beyond the sync
   primitives this scope additionally bans **host-array construction**
   (any ``np.*`` / ``numpy.*`` call, and ``jnp.asarray`` /
   ``jnp.array`` — whose one legitimate chained use, the amortized
   page-horizon refresh, lives in ``_reserve_chain_horizon`` outside
   this scope) and **python loops** (``for`` / ``while`` — a per-slot
   loop is exactly the per-chunk host work the desynchronized decode
   tentpole removed; vectorized reads of the persistent host mirror
   are fine, loops are not).

4. **Packed-submit scope** (ISSUE 25): the two host-stepped submits —
   ``Engine.mixed_step_submit`` and ``Engine._fresh_submit_ee_locked``
   — make ONE host→device transfer each: every descriptor rides a
   packed buffer and the step key is folded inside the program that
   unpacks it. A dozen small uploads and an eager ``fold_in`` with the device
   empty cost 5-9 ms a submit on a v5e (PERF.md §6, PR 25). The scope
   allows at most two upload calls (``jnp.asarray`` / ``jnp.array`` /
   ``jax.device_put``) in the body and bans ``jax.random.*`` and the
   engine's eager ``_next_rng()`` outright.
"""

from __future__ import annotations

import ast

from graftlint.core import Finding, ParsedModule, dotted_name, flag

CHECKER = "jax-hot-path"

# relpath suffix -> function names forming the submit path.
SUBMIT_SCOPES = {
    "serving/engine.py": {
        "decode_chunk_submit", "_scatter_admission", "mixed_step_submit",
        # Structured-outputs admission hooks (ISSUE 13) ride the same
        # dispatch path: registering a grammar span must scatter tables
        # asynchronously, never materialize a device value.
        "structured_register",
    },
    "serving/scheduler.py": {
        "_submit_chunk", "run", "_pass", "_process_handles", "_build_mixed_rows",
    },
    # The mask scatter/upload path (ISSUE 13): grammar spans and
    # logit-bias rows are loaded into the device tables between steps —
    # a host sync here serializes the chunk pipeline against the load,
    # and mask ADVANCEMENT must never host-sync mid-chunk at all (it
    # lives inside the jitted scan, covered by the jit scope).
    "structured/runtime.py": {
        "acquire", "register_slot", "release_slot", "_ensure_live",
    },
}

_SYNC_METHODS = {"item", "block_until_ready"}
_SYNC_DOTTED = {"jax.device_get"}
# jnp.asarray is NOT here: it dispatches asynchronously (device upload);
# only host-side numpy materialization forces a blocking readback.
_NP_SYNC = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}

# Chain-steady scope (ISSUE 14): whole functions forming the host-free
# chained submit. decode_chunk_submit additionally gets its `if chain:`
# branches scanned wherever it is defined (relpath suffix -> names).
CHAIN_STEADY_SCOPES = {
    # _dispatch_chunk_ee_locked is the chained submit's dispatch half,
    # shared with the fresh submit (ISSUE 25).
    "serving/engine.py": {"_chain_submit_locked", "_dispatch_chunk_ee_locked"},
}
# Uploads are banned in the chain-steady scope too: a chained submit
# that jnp.asarray's host data re-introduces the per-chunk h2d the
# tentpole removed (the amortized horizon refresh lives in
# _reserve_chain_horizon, outside this scope).
_CHAIN_UPLOADS = {"jnp.asarray", "jnp.array", "jax.numpy.asarray",
                  "jax.numpy.array"}


# Packed-submit scope (ISSUE 25): relpath suffix -> host-stepped submit
# functions that stage one packed buffer and dispatch one program.
PACKED_SUBMIT_SCOPES = {
    "serving/engine.py": {"mixed_step_submit", "_fresh_submit_ee_locked"},
}
PACKED_SUBMIT_MAX_UPLOADS = 2
_UPLOADS = _CHAIN_UPLOADS | {"jax.device_put"}


def _is_jit_decorated(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        d = dotted_name(dec)
        if d in ("jax.jit", "jit"):
            return True
        if isinstance(dec, ast.Call):
            d = dotted_name(dec.func)
            if d in ("jax.jit", "jit"):
                return True
            if d in ("partial", "functools.partial") and dec.args:
                first = dotted_name(dec.args[0])
                if first in ("jax.jit", "jit"):
                    return True
    return False


def _scope_names(mod: ParsedModule, scopes: dict[str, set[str]]) -> set[str]:
    """The function names a scope table (relpath suffix -> names) gives
    this module."""
    for suffix, names in scopes.items():
        if mod.path.endswith(suffix):
            return names
    return set()


def _scan(fn: ast.AST, mod: ParsedModule, out: list[Finding], *,
          jitted: bool, exclude: set[int] | None = None) -> None:
    where = ("inside a jitted step function" if jitted
             else "in a submit-path function (dispatch must not wait)")
    for node in ast.walk(fn):
        if exclude and id(node) in exclude:
            continue  # already covered by the stricter chain-steady scan
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in _SYNC_METHODS \
                and not node.args:
            flag(out, mod, CHECKER, node,
                 f"host sync '.{func.attr}()' {where}")
            continue
        d = dotted_name(func)
        if d in _SYNC_DOTTED:
            flag(out, mod, CHECKER, node, f"host sync '{d}(...)' {where}")
            continue
        if d in _NP_SYNC:
            flag(out, mod, CHECKER, node,
                 f"'{d}(...)' {where} — materializing a device value "
                 f"here blocks until the computation finishes")
            continue
        if jitted and isinstance(func, ast.Name) and func.id in ("float", "int") \
                and node.args and not isinstance(node.args[0], ast.Constant):
            flag(out, mod, CHECKER, node,
                 f"'{func.id}(...)' on a traced value {where} — a "
                 f"concretization error at trace time")


def _is_chain_test(test: ast.AST) -> bool:
    """True for `if chain:` / `if chain and ...:` — the branch whose body
    is the host-free steady state."""
    if isinstance(test, ast.Name) and test.id == "chain":
        return True
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        return any(_is_chain_test(v) for v in test.values)
    return False


def _scan_chain_steady(nodes, mod: ParsedModule, out: list[Finding],
                       where: str) -> None:
    """The ISSUE 14 host-free rule set: sync primitives as in the submit
    scope, PLUS host-array construction (np.* calls, jnp.asarray/array
    uploads) and python loops — the steady state reads persistent state
    and dispatches, nothing else."""
    for top in nodes:
        for node in ast.walk(top):
            if isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
                flag(out, mod, CHECKER, node,
                     f"python loop {where} — per-slot host iteration is "
                     f"exactly the per-chunk work the host-free steady "
                     f"state removed (vectorize it, or move it to the "
                     f"amortized horizon path)")
                continue
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr in _SYNC_METHODS \
                    and not node.args:
                flag(out, mod, CHECKER, node,
                     f"host sync '.{func.attr}()' {where}")
                continue
            d = dotted_name(func)
            if d in _SYNC_DOTTED:
                flag(out, mod, CHECKER, node, f"host sync '{d}(...)' {where}")
            elif d in _CHAIN_UPLOADS:
                flag(out, mod, CHECKER, node,
                     f"'{d}(...)' {where} — a chained submit must upload "
                     f"nothing; stage device state at chain=False/admission "
                     f"or in the amortized horizon refresh instead")
            elif d is not None and (d.startswith("np.") or d.startswith("numpy.")):
                flag(out, mod, CHECKER, node,
                     f"host-array construction '{d}(...)' {where} — the "
                     f"chained steady state may only read the persistent "
                     f"host mirror, never build arrays per chunk")


def _scan_packed_submit(fn: ast.AST, mod: ParsedModule, out: list[Finding]) -> None:
    """The ISSUE 25 rule set: at most PACKED_SUBMIT_MAX_UPLOADS upload
    calls, and no eager key derivation (the key is drawn in the
    program from a counter that rides the packed buffer)."""
    where = "in a packed host-stepped submit (packed-submit scope)"
    uploads = 0
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        d = dotted_name(node.func)
        if d in _UPLOADS:
            uploads += 1
            if uploads > PACKED_SUBMIT_MAX_UPLOADS:
                flag(out, mod, CHECKER, node,
                     f"upload #{uploads} '{d}(...)' {where} — each small "
                     f"upload is a transfer with the device waiting; write "
                     f"the field into the packed buffer instead")
        elif d is not None and (d.startswith("jax.random.") or d == "self._next_rng"):
            flag(out, mod, CHECKER, node,
                 f"eager key derivation '{d}(...)' {where} — a dispatch "
                 f"of its own; fold the key inside the step program from "
                 f"the counter in the packed buffer")


def check(mod: ParsedModule) -> list[Finding]:
    out: list[Finding] = []
    submit_names = _scope_names(mod, SUBMIT_SCOPES)
    chain_names = _scope_names(mod, CHAIN_STEADY_SCOPES)
    packed_names = _scope_names(mod, PACKED_SUBMIT_SCOPES)
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _is_jit_decorated(fn):
            _scan(fn, mod, out, jitted=True)
            continue
        if fn.name in chain_names:
            _scan_chain_steady(
                fn.body, mod, out,
                "in the host-free chained-submit path (chain-steady scope)")
            continue
        chain_covered: set[int] = set()
        if fn.name == "decode_chunk_submit" and chain_names:
            # Branch-aware: the `if chain:` bodies are chain-steady even
            # though the surrounding fresh-submit path legitimately
            # builds host arrays. Nodes covered here are excluded from
            # the broader submit scan below so one defect never yields
            # two findings.
            for node in ast.walk(fn):
                if isinstance(node, ast.If) and _is_chain_test(node.test):
                    _scan_chain_steady(
                        node.body, mod, out,
                        "in the chain=True branch of decode_chunk_submit "
                        "(chain-steady scope)")
                    for top in node.body:
                        chain_covered.update(id(n) for n in ast.walk(top))
        if fn.name in packed_names:
            _scan_packed_submit(fn, mod, out)
        if fn.name in submit_names:
            _scan(fn, mod, out, jitted=False, exclude=chain_covered)
    return out
