"""How close a decode step of a hybrid configuration comes to the memory
roofline: the bytes a step must read (``hybrid_bytes.step_bytes``: the matrices
and the tied head as the program holds them; for the rows that were decoding
while the profiler ran, from the client's own records, 8 x min(context, window)
+ 8 x context cached tokens a row and the row's state read and written) over
the chip's peak bandwidth, divided by the device time of one decode step: the
decode program's median duration in the trace over the steps it makes.
Memory-bound. Nothing for a configuration without a state (``mamba_d_state``),
or without a trace."""
from hybrid_bytes import is_hybrid, live_rows, sizes, step_bytes

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("decode_chunk_s") or not is_hybrid(config):
        return None
    step_s = trace["decode_chunk_s"] / int(config["deployment_sizes"]["decode_chunk"])
    t0 = trace["mono_start"]
    rows, contexts, seen = live_rows(sources["all_requests"], t0, t0 + trace["window_s"], sizes(config)["window"])
    return 100.0 * (step_bytes(config, rows, contexts, seen) / sources["chip"]["hbm_bytes_per_s"]) / step_s
