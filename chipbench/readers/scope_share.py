"""Share of the device's self time spent under one of the program's
`jax.named_scope`s (`chipbench/scopes.py`), read from the `op_name` of
each event of the `XLA Ops` line.

Arguments: `scope`, one of `scopes.SCOPES`, or "" for the share under no
scope at all (what the scopes cannot see, as a number); `exclude`, a
piece of an operation's own name that rules it out (the kernel, where
the metric is the overhead around it). The events handed to the readers
carry no stats, so this one opens the trace file itself, once a run.
"""
from .. import scopes


def read(record, trace, args):
    path = record.get("trace_path")
    if trace is None or not path:
        return None
    if "scoped_ops" not in record:
        record["scoped_ops"] = scopes.load(path)
    return scopes.share(record["scoped_ops"], args["scope"],
                        args.get("exclude"))
