"""How close a decode step of an ``lfm2_moe`` configuration comes to the memory
roofline: the bytes a step must read, a lower bound counted from the file's
sizes and the chunk's own ``decode`` record (``lfm2_bytes.decode_step_bytes``:
every operator's matrices, the dense prefix, the routers, the tied head as
held, the distinct experts its rows chose, the pages its rows hold in the
caching layers, each row's state read and written), over the chip's peak
bandwidth, divided by the device time of one decode step: the decode program's
median duration in the trace (``trace["decode_chunk_s"]``) over the steps it
makes (``deployment_sizes.decode_chunk``). The bytes are the median over the
``decode`` records dispatched inside the capture, as the time is a median.
Memory-bound: 64 rows x 4 choices multiply 1.5 G weights a row, 0.2 TFLOP a
step, 1 ms at the chip's bf16 peak, where the bytes take 11. Nothing for another
configuration, without a trace, or where a record lacks a counter; over 100% is
a fault of the count."""
from lfm2_bytes import decode_step_bytes, is_lfm2
from stats import dispatched_in_capture, median

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("decode_chunk_s") or not is_lfm2(config):
        return None
    records = dispatched_in_capture(sources)
    if records is None:
        return None
    counts = [decode_step_bytes(config, t) for t in records if t.get("kind") == "decode"]
    if not counts or any(b is None for b in counts):
        return None
    step_s = trace["decode_chunk_s"] / int(config["deployment_sizes"]["decode_chunk"])
    return 100.0 * (median(counts) / sources["chip"]["hbm_bytes_per_s"]) / step_s
