"""A counter of seconds the program keeps, as a share of the window:
its difference around the window over the window's length."""


def read(record, trace, args):
    delta = record.get("counters", {}).get(args["counter"])
    if delta is None or not record.get("window_s"):
        return None
    return 100.0 * delta / record["window_s"]
