"""A percentile of a list of times the driver took on its own clock."""
from .. import common


def read(record, trace, args):
    values = record.get(args["key"])
    if not values:
        return None
    return common.percentile(values, float(args["percentile"]))
