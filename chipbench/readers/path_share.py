"""Share of the device's self time in operations whose `op_name` has one
of the given components: the sub-scopes a program nests inside the
scopes of `chipbench/scopes.py` (`mlp/moe_experts/...`), which that
list does not hold. Read as `scope_share` reads: from the `op_name` of
each event of the `XLA Ops` line, over `scopes.load` and
`scopes.self_seconds`.

Arguments: `components`, the names of which any one, as a whole
component of the path, counts an operation in. Where no operation has
any of them (a program that lacks the sub-scopes, another model) the
reader returns nothing.
"""
from .. import scopes


def read(record, trace, args):
    path = record.get("trace_path")
    if trace is None or not path:
        return None
    if "scoped_ops" not in record:
        record["scoped_ops"] = scopes.load(path)
    ops = record["scoped_ops"]
    wanted = set(args["components"])
    own = scopes.self_seconds(ops)
    total = sum(own)
    took = sum(s for op, s in zip(ops, own)
               if wanted.intersection(op.op_name.split("/")))
    if total <= 0 or took <= 0:
        return None
    return 100.0 * took / total
