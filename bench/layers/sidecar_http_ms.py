"""The sidecar's own HTTP handling on the way to the first token, per request
from its access-log line: ``recv_to_submit_ms`` (body parse, chat template,
tokenise, before the scheduler's clock starts) + ``first_token_to_write_ms``
(the scheduler's first token to the first content frame handed to the
transport); the median over the window's requests. The part of
``gateway_ttft_overhead_ms`` that is not the gateway's. Nothing on a program
whose lines lack the two fields."""
from stats import measured, median

LAYER = "sidecar scheduler"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "access_log"


def compute(sources):
    log = sources["access_log"]
    out = []
    for r in measured(sources):
        ev = log.get(r.trace_id) or {}
        if ev.get("recv_to_submit_ms") is not None and ev.get("first_token_to_write_ms") is not None:
            out.append(ev["recv_to_submit_ms"] + ev["first_token_to_write_ms"])
    return median(out)
