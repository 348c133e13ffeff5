"""Sidecar start: what the program compiled AFTER its warm-up bracket closed, in
seconds: the sum of ``compile_ms`` over ``status.device.compile.recompile_events``
(the compile ledger classes a compile after ``Engine.warmup`` as a recompile).
A step program that the first request of a kind lowers again, on the scheduler
thread, is start-up time the warm-up did not pay: the benchmark's first biased
request lowered the mixed step and the decode chunk a second time (ISSUE 49).
0.0, never nothing, where the ledger is there and holds no event (the ledger
keeps the last 32 events; a start makes two or none). Nothing on a program
whose status has no ledger."""

LAYER = "sidecar start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "status"


def compute(sources):
    ledger = ((sources.get("status") or {}).get("device") or {}).get("compile")
    if not isinstance(ledger, dict) or "recompile_events" not in ledger:
        return None
    return sum(float(e.get("compile_ms", 0.0)) for e in ledger["recompile_events"]) / 1e3
