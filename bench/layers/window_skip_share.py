"""Share of their rows' pages that the windowed layers did NOT read: 1 -
``window_pages_read`` / ``context_pages`` over the window's ``decode`` records of
``/debug/timeline``, the program's own counts (both summed over a record's steps
and the windowed layers). 0 while every context is inside the window; it rises
as rows decode past it, which is what says the kernels' window path is engaged.
Nothing where no record carries the counts."""

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def compute(sources):
    recs = [t for t in sources.get("timeline") or []
            if t.get("kind") == "decode" and t.get("context_pages")]
    held = sum(t["context_pages"] for t in recs)
    return 100.0 * (1.0 - sum(t["window_pages_read"] for t in recs) / held) if held else None
