"""How close a decode step of an ``evabyte`` configuration comes to the memory
roofline: the bytes a step must read, a lower bound counted from the file's sizes and
the chunk's own record (``eva_bytes.decode_step_bytes``: every int8 matrix once, the
head, and the record's ``eva_rows`` a step, a key and a value a layer: the rows of the
pool, NOT a row a token, which is what ``decode_hbm_roofline_share`` would count),
over the chip's peak bandwidth, divided by the decode chunk program's device time a
step (``trace_classes.json`` ``modules.decode_chunk``: its median duration in the
trace, over ``decode_chunk`` steps; a chunk that left early made fewer, which a closed
loop with every slot busy does not). The bytes are the median over the ``decode``
records dispatched inside the capture. Nothing for another configuration, without a
trace, or where a record lacks a counter; over 100% is a fault of the count."""
from eva_bytes import decode_step_bytes, is_eva, sizes, step_share

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    chunk = (sources.get("trace") or {}).get("decode_chunk_s")
    if not chunk or not is_eva(sources["config"]):
        return None
    return step_share(sources, "decode", decode_step_bytes, chunk / sizes(sources["config"])["steps"], "hbm_bytes_per_s")
