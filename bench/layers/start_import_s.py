"""Sidecar start: the process's first instruction to its imports done
(``status.startup.import_s``). Nothing on a program that does not time its start."""

LAYER = "sidecar start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "status"


def compute(sources):
    return ((sources.get("status") or {}).get("startup") or {}).get("import_s")
