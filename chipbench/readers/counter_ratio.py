"""One counter of the program over another, each as its difference
around the window: `over` by `under`, times `scale`. Where the program
has no such counter, or `under` did not move, the reader returns
nothing."""


def read(record, trace, args):
    counters = record.get("counters", {})
    over, under = counters.get(args["over"]), counters.get(args["under"])
    if over is None or not under:
        return None
    return float(args.get("scale", 1.0)) * over / under
