"""How close the selective scan's chunk kernel (``ssm_scan_chunk_kernel``: a
mixed step's rows of more than one token, a visit 64 packed tokens of a row, the
row's state in VMEM across its visits) comes to its roofline: the greater of its
bytes over the peak bandwidth and its operations over the chip's bf16 peak
(``mamba1_bytes.chunk_work``: ``ssm_chunk_tokens`` of the ``mixed`` records
dispatched inside the capture x 61,568 B (Delta, c, y, B and C; the lane-replicated
columns the kernel is handed are its time, not its work) and x 737,280 operations a
token a layer, ``ssm_chunk_rows`` x a state once in and once out, at the published
sizes), over that kernel's own device time in the trace. The form of
``ssd_chunk_roofline_share``, a LOWER bound: the kernel's body is vector and
transcendental-unit work (an exponential, three multiply-adds and a term of a
sum for each of 81,920 state entries a token a layer; nothing becomes a matrix
product), so neither the bandwidth nor the matrix unit is its ceiling. Alone on
the chip (``hack/ssm_scan_bench.py``, my chip run, PR 64) a 512-token row took
152.5 us a layer (159.7 as it stands, its channel groups in a loop) where these
bytes take 39.3 us and a count of its vector operations at four a cycle 92.6
us: the kernel runs at 58-61% of the vector unit's ceiling, which is 25-26% of
this bound: a reading near 25% is the vector unit nearly two thirds busy, not
idle bandwidth. Nothing for another configuration,
without a trace, where a record lacks the counters or the trace holds no such
kernel (the parent commit); over 100% is a fault of the count."""
from mamba1_bytes import CHUNK_KERNEL, chunk_work, is_jamba
from mla_bytes import kernel_pattern, op_times
from stats import dispatched_in_capture

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not is_jamba(config):
        return None
    work = chunk_work(config, dispatched_in_capture(sources))
    times = op_times(trace, "ssm_scan_chunk_kernel", kernel_pattern(CHUNK_KERNEL))
    if work is None or times is None:
        return None
    chip = sources["chip"]
    return 100.0 * max(work[0] / chip["bf16_flops_per_s"], work[1] / chip["hbm_bytes_per_s"]) / times["seconds"]
