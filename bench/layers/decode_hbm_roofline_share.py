"""How close a decode step comes to the memory roofline: the bytes one step must
read (the int8 matrices once, plus the keys and values of the contexts that
were decoding while the profiler ran, counted from the client's own records)
over the chip's peak bandwidth, divided by the device time of one decode step:
the decode program's median duration in the trace over the steps it makes.
Decode is memory-bound: its operations would take a fraction of that time."""
from ops_bytes import kv_bytes_per_token, weight_bytes_per_step
from stats import mean_live_context

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace+client"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("decode_chunk_s"):
        return None
    config = sources["config"]
    step_s = trace["decode_chunk_s"] / int(config["deployment_sizes"]["decode_chunk"])
    t0 = trace["mono_start"]
    context, _rows = mean_live_context(sources["all_requests"], t0, t0 + trace["window_s"])
    need = weight_bytes_per_step(config) + context * kv_bytes_per_token(config)
    return 100.0 * (need / sources["chip"]["hbm_bytes_per_s"]) / step_s
