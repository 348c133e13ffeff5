"""How close the one-query rows' absorbed read of the latent pages
(``mla_decode_attn_kernel``: a mixed step's decode rows and the decode chunk's)
comes to its roofline: the greater of the latent bytes those rows must read
over the chip's peak bandwidth and the absorbed read's operations over its
bf16 peak (``mla_bytes.row_read``: ``latent_row_pages_read`` of the records
dispatched inside the capture x page size x 1,152 B, and x heads x 2 x (2 x
kv_lora_rank + rope) operations), over that kernel's own device time in the
trace. 32 heads on one key row is 60 operations a byte, under the chip's ridge
of 240: the bytes bound it unless the latent is read more than once. Nothing
for another configuration, without a trace, where a record lacks the counter or
the trace holds no such kernel (the parent commit); over 100% is a fault of the
count."""
from mla_bytes import DECODE_KERNEL, is_xing, kernel_pattern, op_times, row_read
from stats import dispatched_in_capture

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not is_xing(config):
        return None
    records = dispatched_in_capture(sources)
    read = row_read(config, [t for t in records or [] if t.get("kind") in ("mixed", "decode")])
    times = op_times(trace, "mla_decode_kernel", kernel_pattern(DECODE_KERNEL))
    if read is None or times is None:
        return None
    chip = sources["chip"]
    return 100.0 * max(read[0] / chip["hbm_bytes_per_s"], read[1] / chip["bf16_flops_per_s"]) / times["seconds"]
