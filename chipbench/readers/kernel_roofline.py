"""A kernel's share of its roofline: the least time the chip could take
for the work the algorithm needs (the larger of operations over peak
FLOP/s and bytes over peak bytes/s, from `flops.py` through the
driver's record), over the time the kernel's events took in the trace.

Arguments: `match`, a piece of the name the compiler gives the kernel's
events today (all of them: forward and backward); `work`, the key of
the record's `kernel_work`. That holds either one unit's `flops` and
`bytes`, and then `count` is a piece of the name that only one event of each unit
carries and `skip` a piece that rules an event out of the count; or the
`flops_per_s` and `bytes_per_s` the window's work needed, where the
units differ from call to call, and then the least time is that rate
over the traced window. Where the trace has no such events the reader
returns nothing.
"""
from .. import trace as tr


def read(record, trace, args):
    work = record.get("kernel_work", {}).get(args["work"])
    if trace is None or not work:
        return None
    seconds, units = 0.0, 0
    for ops in tr.device_ops(trace).values():
        seconds += tr.named_time(ops, [args["match"]])[0]
        if "count" in args:
            names = [tr.own_name(e.name) for e in ops]
            units += sum(
                1 for n in names if args["count"] in n
                and not (args.get("skip") and args["skip"] in n))
    if seconds <= 0:
        return None
    peaks = record["peaks"]
    if "flops" in work:
        least = units * max(work["flops"] / peaks["bf16_flops_per_s"],
                            work["bytes"] / peaks["hbm_bytes_per_s"])
    else:
        least = tr.busy_and_window(trace)[1] * max(
            work["flops_per_s"] / peaks["bf16_flops_per_s"],
            work["bytes_per_s"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if least > 0 else None
