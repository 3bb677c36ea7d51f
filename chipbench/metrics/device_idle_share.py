"""Share of the traced window in which no operation ran on the device (%):
1 - (union of the device's operation intervals) / window, from the
profiler trace."""


def read(record):
    if record.trace is None:
        return None
    return 100.0 * record.trace.idle_share
