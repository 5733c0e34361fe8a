"""Set-up's compile accounting, from the program's own counters of the
process-wide registry (`paddle_tpu/observability/compiles.py`): the
seconds JAX spent building the program's executables over some phases,
how many executables it built, or how many of those the persistent
compile cache did not hold. A compile on another backend says nothing
of the chip's set-up, so off a TPU the reader returns nothing; it
returns nothing too where the program keeps no such counter.

Arguments: `read`, one of `seconds` (summed over `phases`, of `trace`,
`lower` and `backend`), `executables`, `misses`.
"""
import jax

SECONDS = "jit_compile_seconds_total"
EXECUTABLES = "jit_executables_total"


def read(record, trace, args):
    if jax.default_backend() != "tpu":
        return None
    from paddle_tpu.observability import global_registry

    kind = args["read"]
    name = SECONDS if kind == "seconds" else EXECUTABLES
    series = [m for m in global_registry().collect() if m.name == name]
    if not series:
        return None
    if kind == "seconds":
        series = [m for m in series if m.labels["phase"] in args["phases"]]
    elif kind == "misses":
        series = [m for m in series if m.labels["cache"] != "hit"]
    return float(sum(m.value for m in series))
