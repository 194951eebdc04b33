"""Device program launches in the traced window per read completed."""


def read(run):
    if run.trace is None or not run.window.items:
        return None
    return len(run.trace.modules()) / len(run.window.items)
