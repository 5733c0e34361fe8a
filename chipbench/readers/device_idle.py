"""Share of the traced window in which no operation ran on the device:
1 - union of the device operations' intervals over the window, averaged
over the chips."""
from .. import trace as tr


def read(record, trace, args):
    if trace is None:
        return None
    busy, window = tr.busy_and_window(trace)
    if window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
