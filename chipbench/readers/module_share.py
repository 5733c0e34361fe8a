"""Share of the device's time in executables of one family: the events
of the `XLA Modules` line whose name holds `match` (`jit_prefill` for
`jit_prefill`, `jit_prefill_offset` and `jit_prefill_chunk`), over all
events of that line. Where no module matches the reader returns
nothing: a name that went away must not read as no time."""
from .. import trace as tr

MODULES_LINE = "XLA Modules"


def read(record, trace, args):
    if trace is None:
        return None
    total = matched = 0.0
    for e in trace:
        if e.plane.startswith(tr.DEVICE_PLANE) and e.line == MODULES_LINE:
            total += e.duration
            if args["match"] in e.name:
                matched += e.duration
    if matched <= 0:
        return None
    return 100.0 * matched / total
