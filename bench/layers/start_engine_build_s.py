"""Sidecar start: building the engine's device state, from ``status.startup``:
parameters built (``params_s``; for a large quantised model, built quantised)
+ ``quantize_s`` + the KV pool (``kv_pool_s``) + the structured-output tables
(``structured_s``), each closed by a wait for the device. Warm-up, imports and
the runtime's own start are other stages. Nothing on a program that does not
time its start."""

LAYER = "sidecar start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "status"

STAGES = ("params_s", "quantize_s", "kv_pool_s", "structured_s")


def compute(sources):
    startup = (sources.get("status") or {}).get("startup")
    if not startup or any(k not in startup for k in STAGES):
        return None
    return sum(startup[k] for k in STAGES)
