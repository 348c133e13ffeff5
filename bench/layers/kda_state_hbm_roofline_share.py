"""How close Kimi Delta Attention's one-token update (a mixed step's one-token
rows and the decode chunk's rows: each row's 2 MB matrix state read and written
in each of the six KDA layers) comes to the memory roofline: the bytes it must
move (``kda_bytes.update_bytes``: ``state_rows`` of the ``mixed`` and ``decode``
records dispatched inside the capture, a ``decode`` record's summed over its
steps by the program, x 6 layers x 2 x 2,097,152 B) over the chip's peak
bandwidth, over the device time of the kernel ``kda_state_update_kernel``
(``update_pattern``: by name). The kernel reads and writes the advancing rows'
states once (a slot that does not advance costs a grid step and no bytes).
Nothing for another configuration, without a trace, where a record lacks the
counter or the trace holds no such operation (the parent commit); over 100% is
a fault of the count."""
from kda_bytes import is_kimi, update_bytes, update_pattern
from mla_bytes import op_times
from stats import dispatched_in_capture

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not is_kimi(config):
        return None
    moved = update_bytes(config, dispatched_in_capture(sources))
    times = op_times(trace, "kda_state_update_ops", update_pattern(config))
    if moved is None or times is None:
        return None
    return 100.0 * (moved / sources["chip"]["hbm_bytes_per_s"]) / times["seconds"]
