"""How close the blocked (SSD) scan's kernel (``ssd_chunk_kernel``: a mixed
step's rows of more than one token, a visit a packed block of 128 tokens a row)
comes to its roofline: the greater of its block products' operations over the
chip's bf16 peak and its blocks' bytes over the peak bandwidth
(``mamba2_bytes.chunk_work``: ``ssd_blocks`` of the ``mixed`` records dispatched
inside the capture x 809 MFLOP and x 8.5 MB a visit at the published sizes),
over that kernel's own device time in the trace. 95 operations a byte is under
the chip's ridge of 240, and the kernel multiplies in float32 at the highest
precision (several passes of the bf16 unit a product, so that the carried state
does not drift), beside a Q x Q exponential a head a visit on the vector unit:
a low share is those, not idle bandwidth. Nothing for another configuration,
without a trace, where a record lacks the counter or the trace holds no such
kernel (the parent commit); over 100% is a fault of the count."""
from mamba2_bytes import CHUNK_KERNEL, chunk_work, is_granite
from mla_bytes import kernel_pattern, op_times
from stats import dispatched_in_capture

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not is_granite(config):
        return None
    work = chunk_work(config, dispatched_in_capture(sources))
    times = op_times(trace, "ssd_chunk_kernel", kernel_pattern(CHUNK_KERNEL))
    if work is None or times is None:
        return None
    chip = sources["chip"]
    return 100.0 * max(work[0] / chip["bf16_flops_per_s"], work[1] / chip["hbm_bytes_per_s"]) / times["seconds"]
