"""Signature sets verified per second: the sets of every request that got
its verdict in the window, over the whole window (host clock)."""


def read(run):
    return sum(r["sets"] for r in run.answered) / run.window_s if run.window_s > 0 else None
