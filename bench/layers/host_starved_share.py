"""Share of the whole window in which the device had nothing queued although
the scheduler had work: the sum of ``starved_ms`` over every timeline record of
the window (each dispatch that found no program in flight records how long that
had been so while a slot was active or a request waited) over the window's
seconds. The program's own counter of what ``device_idle_share`` sees from the
trace, and it covers all of the window, not the capture's 3 s. Nothing on a
program whose records carry no ``starved_ms``."""

LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "timeline"


def compute(sources):
    starved = [t["starved_ms"] for t in sources["timeline"] if t.get("starved_ms") is not None]
    seconds = (sources.get("window") or {}).get("seconds")
    if not starved or not seconds:
        return None
    return 100.0 * sum(starved) / 1e3 / seconds
