"""clock-discipline: all timing logic goes through the injectable clock.

PR 1 made every resilience policy (breaker cooldowns, backoff sleeps,
deadline budgets, stream-idle guards) read time exclusively through a
clock object so tests drive the whole layer on a ``VirtualClock`` with
zero real sleeps. This checker makes that a project-wide invariant:
direct ``time.time()`` / ``time.monotonic()`` / ``time.sleep()`` calls
are banned outside a small allowlist.

Allowlisted modules (the designated real-time sites):

- ``resilience/clock.py``  — the injectable clock *implementation*
- ``otel/profiling.py``    — sampling-profiler / stall-watchdog daemon
  threads measure real wall time by definition
- ``logger.py``            — the log-flush daemon thread

Not banned: ``time.time_ns()`` (epoch span/phase stamps — wire formats
need wall-clock epochs) and ``time.perf_counter()`` (profiling stamps).
A genuinely-wall-clock site outside the allowlist (e.g. a JWT ``exp``
check) takes a reasoned ``# graftlint: disable=clock-discipline``.
"""

from __future__ import annotations

import ast

from graftlint.core import Finding, ParsedModule, dotted_name, flag

CHECKER = "clock-discipline"

BANNED = {
    "time.time": "time.time() — inject the clock (or time_ns for epoch stamps)",
    "time.monotonic": "time.monotonic() — inject the clock (clock.now())",
    "time.sleep": "time.sleep() — inject the clock (await clock.sleep())",
}

ALLOWLIST = (
    "inference_gateway_tpu/resilience/clock.py",
    "inference_gateway_tpu/otel/profiling.py",
    "inference_gateway_tpu/logger.py",
)


def _from_time_imports(tree: ast.Module) -> dict[str, str]:
    """Local alias -> dotted name, for ``from time import sleep [as s]``."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                aliases[a.asname or a.name] = f"time.{a.name}"
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "time" and a.asname:
                    aliases[a.asname] = "time"
    return aliases


def check(mod: ParsedModule) -> list[Finding]:
    if mod.path.endswith(ALLOWLIST):
        return []
    out: list[Finding] = []
    aliases = _from_time_imports(mod.tree)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = dotted_name(node.func)
        if dotted is None:
            continue
        head, _, rest = dotted.partition(".")
        if head in aliases:
            dotted = aliases[head] + ("." + rest if rest else "")
        if dotted in BANNED:
            flag(out, mod, CHECKER, node,
                 f"direct wall-clock call: {BANNED[dotted]}")
    return out
