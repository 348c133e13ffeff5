"""How close Kimi Delta Attention's chunk kernel (``kda_chunk_kernel``: a mixed
step's rows of more than one token, a visit a packed block of 64 tokens a row)
comes to its roofline: the greater of its block products' operations over the
chip's bf16 peak and its blocks' bytes over the peak bandwidth
(``kda_bytes.chunk_work``: ``kda_blocks`` of the ``mixed`` records dispatched
inside the capture x 268 MFLOP and x 5.2 MB a visit at the published sizes),
over that kernel's own device time in the trace. 51 operations a byte is under
the chip's ridge of 240; the kernel forms L and solves in float32 at the
highest precision (several passes of the bf16 unit a product) and, because the
decay differs by channel, forms every pair of a sub-block of 16 tokens alone on
the vector unit (an exponential and two lane sums a pair a head): a low share is
those, not idle bandwidth. Nothing for another configuration, without a trace,
where a record lacks the counter or the trace holds no such kernel (the parent
commit); over 100% is a fault of the count."""
from kda_bytes import CHUNK_KERNEL, chunk_work, is_kimi
from mla_bytes import kernel_pattern, op_times
from stats import dispatched_in_capture

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not is_kimi(config):
        return None
    work = chunk_work(config, dispatched_in_capture(sources))
    times = op_times(trace, "kda_chunk_kernel", kernel_pattern(CHUNK_KERNEL))
    if work is None or times is None:
        return None
    chip = sources["chip"]
    return 100.0 * max(work[0] / chip["bf16_flops_per_s"], work[1] / chip["hbm_bytes_per_s"]) / times["seconds"]
