"""How close the expert layer of a decode step comes to the memory roofline:
the bytes of the distinct experts a step read (``experts_read`` of the decode
records dispatched inside the capture x one expert's three matrices x the
layers, ``moe_bytes.expert_bytes_per_step``) over the chip's peak bandwidth,
divided by the expert kernels' device time in one decode step: the time of the
``moe_*_gmm`` operations that ran inside a decode program (told by the program
event that encloses them, ``moe_bytes.expert_kernel_times``), over the steps
those programs made (programs x ``decode_chunk``; a chunk that left early made
fewer, which a closed loop with every slot busy does not). Memory-bound at a
few rows an expert; a lower bound (router, scales and the rows themselves are
not counted). Nothing where the trace holds no expert kernel, no record carries
the count, or the mesh is not the file's. Expert kernels and decode programs in
one trace with none of the first inside the second is a renamed kernel or
program, and raises: a share of the wrong operations is worse than none."""
from moe_bytes import expert_bytes_per_step, expert_kernel_times, experts_read_in_capture
from ops_bytes import has_experts, sizes

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def compute(sources):
    trace, config = sources.get("trace"), sources["config"]
    if not trace or not has_experts(config):
        return None
    mesh = (sources.get("status") or {}).get("mesh") or {}
    if mesh.get("tp") != sizes(config)["tp"] or mesh.get("ep", 1) != 1:
        return None
    times = expert_kernel_times(trace)
    reads = experts_read_in_capture(sources)
    if times is None or reads is None or not times["decode_programs"]:
        return None
    if not times["decode_s"]:
        raise RuntimeError(f"{times['moe_events']} expert kernel events and {times['decode_programs']} decode "
                           "programs in the trace, but no kernel inside a decode program")
    step_s = times["decode_s"] / (times["decode_programs"] * int(config["deployment_sizes"]["decode_chunk"]))
    return 100.0 * (expert_bytes_per_step(config, reads) / sources["chip"]["hbm_bytes_per_s"]) / step_s
