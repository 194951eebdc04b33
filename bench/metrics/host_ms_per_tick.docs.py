"""Host time per scheduler step that the device spent idle: each step's
wall time minus the device-busy time inside it, averaged, in ms."""

from bench import readers


def read(run):
    return readers.host_ms_per_tick(run)
