"""Seconds from process start until the window opened: imports, device
start, data, and every program the window runs traced, lowered and compiled
or loaded from the persistent cache (host clock)."""


def read(run):
    return run.setup_s
