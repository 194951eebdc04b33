"""Tile calls of the align stage's wavefront walks per read completed:
the service's ``align_tiles`` counter, end minus start, over the reads
the window completed."""


def read(run):
    tiles = run.window.counters.get("align_tiles")
    if not tiles or not run.window.items:
        return None
    return tiles / len(run.window.items)
