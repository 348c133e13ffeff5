"""What one mixed step costs the host, from the program's own step spans: per
``mixed`` record of the timeline the sum of its phases ``build`` (the
scheduler's row descriptors), ``lock_wait``, ``pages`` (page growth and flat
write indices), ``assemble`` (the numpy fills), ``upload_dispatch`` (mask
arguments, the rng key, the uploads and the jitted call returning) and ``emit``
(callbacks, flushes, slot release); the median over the window. ``fetch_wait``
is left out: that is the device's time. Nothing on a program without phases."""
from stats import median

LAYER = "sidecar scheduler"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "timeline"

HOST_PHASES = ("build", "lock_wait", "pages", "assemble", "upload_dispatch", "emit")


def compute(sources):
    return median([sum(t["phases"].get(k, 0.0) for k in HOST_PHASES)
                   for t in sources["timeline"] if t.get("kind") == "mixed" and t.get("phases")])
