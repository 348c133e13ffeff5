"""Share of their rows' pages that a mixture's windowed layers did NOT read: 1 -
``window_pages_read`` / ``context_pages`` over the window's ``decode`` AND
``mixed`` records of ``/debug/timeline``, the program's own counts (both summed
over a record's rows, steps and windowed layers: ``afmoe_bytes.page_skip_share``).
The accepted ``window_skip_share`` reads ``decode`` records alone; a closed loop
of long prompts makes nearly only ``mixed`` ones. 0 while every context is inside
the window; with contexts of 2-6 windows both kernels walk 16-21 of a row's
32-100 pages. The pages behind the window are still HELD (one kind of page):
this is what an allocator that gave them back would free. Nothing where no
record carries the counts."""
from afmoe_bytes import page_skip_share

LAYER = "engine and model step"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def compute(sources):
    return page_skip_share(sources.get("timeline"))
