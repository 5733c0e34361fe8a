"""The whole step's share of the chips' peak: the operations the
algorithm needs for the work done in the window (`flops.py`), over the
window, over chips x peak bf16 FLOP/s of `peaks.json`."""


def read(record, trace, args):
    flops, seconds = record.get("model_flops"), record.get("window_s")
    if not flops or not seconds:
        return None
    peak = record["peaks"]["bf16_flops_per_s"] * record["chips"]
    return 100.0 * flops / seconds / peak
