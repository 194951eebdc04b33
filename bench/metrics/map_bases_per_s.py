"""Bases of every read completed in the window, over the window."""


def read(run):
    return sum(it["bases"] for it in run.window.items) / run.window.seconds
