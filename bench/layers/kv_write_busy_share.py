"""Share of the device's busy time under the scope ``kv.write``: a step's keys and
values into the flat pools (``models/llama._write_kv``; since ISSUE 62
``ops/pool_write.py``'s copies of the runs the rows lie in where its dispatch says
so, XLA's scatter elsewhere), and in an EVA model the fold of a window into a page
of summaries, which stands under the same scope. Self time by named scope, both
step programs together (``scope_times.by_scope``, read off the capture's own HLO).
Nothing where no operation of either step program stands under the scope (the
families that write their pool themselves), or where the capture's HLO could not
be had."""
from scope_times import STEP_MODULES, by_scope

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"
SCOPE = "kv.write"


def compute(sources):
    trace = sources.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    got = by_scope(trace)
    if got is None:
        return None
    scopes = [(got["modules"].get(key) or {}).get("scope_s") or {} for key in STEP_MODULES]
    if not any(SCOPE in s for s in scopes):
        return None
    return 100.0 * sum(s.get(SCOPE, 0.0) for s in scopes) / trace["busy_s"]
