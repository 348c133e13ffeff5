"""How much of the device's idle time inside the profiler's capture the
program's own starvation spans do not account for: 1 - (sum of ``starved_ms``
of the timeline records dispatched inside the capture) / (the trace's window -
its busy time), floored at 0. The capture is placed on the records' clock by
``status.jax_trace`` (``capture_epoch_ns``: start_trace returned -> stop_trace
called; else ``start_epoch_ns`` -> ``stop_epoch_ns``); a record is dispatched at
its ``submit_ts`` (a decode chunk, submitted a chunk or two before the record's
own start) or else at its ``start_ts``. A record the capture's edge cuts counts
whole or not at all, by where its dispatch falls. High means the spans miss
where the device waits: a defect of the spans. Nothing on a program that does
not say where the capture lay."""

LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "timeline+device_trace"


def compute(sources):
    trace = sources.get("trace")
    capture = (sources.get("status") or {}).get("jax_trace")
    if not trace or not capture or not trace.get("window_s"):
        return None
    lo, hi = capture.get("capture_epoch_ns") or (capture["start_epoch_ns"], capture["stop_epoch_ns"])
    idle_s = trace["window_s"] - trace["busy_s"]
    inside = [t["starved_ms"] for t in sources["timeline"] if t.get("starved_ms") is not None
              and lo <= (t.get("submit_ts") or t["start_ts"]) * 1e9 <= hi]
    if idle_s <= 0 or not inside:
        return None
    return 100.0 * max(1.0 - sum(inside) / 1e3 / idle_s, 0.0)
