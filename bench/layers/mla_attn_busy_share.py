"""Share of the device's busy time spent in the latent attention's own
operations of a ``xing4_0`` configuration, decode program and mixed step
together (``mla_bytes.attention_pattern``: the two kernels by name,
``mla_decode_attn_kernel`` and ``mla_chunk_attn_kernel``, and the operations
that hold an array a head wide by the latent's row or a token's pool row: the
absorption of the queries, the weighted latents' way up, the pool's write). The
five matrices' products have any projection's shapes and are not counted: a
lower bound. Nothing for another configuration, or where the trace holds no
such operation (the parent commit)."""
from mla_bytes import attention_pattern, is_xing, op_times

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not trace.get("busy_s") or not is_xing(config):
        return None
    times = op_times(trace, "mla_attention_ops", attention_pattern(config))
    return None if times is None else 100.0 * times["seconds"] / trace["busy_s"]
