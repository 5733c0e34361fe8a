"""One run of one cell:

    python3 -m chipbench.run --workload W --seed N --seconds S --trace 0|1

The last line of standard output is the result: one JSON object. With
`--trace 0` its metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of a
slice of the window, from the driver's own clock and from the program's
counters. There is no CPU mode: without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero before any work.
"""
from __future__ import annotations

import time

_STARTED = time.time()      # set-up runs from here to the window's start

import argparse     # noqa: E402
import json         # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402


def _parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def measure(spec_: dict, seed: int, seconds: float, trace: bool,
            device: dict, started: float) -> dict:
    """Drive the cell and build the result object. `device` is as JAX
    reports it; nothing here looks for a chip, so a test can call it."""
    from . import common, spec, trace as tr

    driver = spec.load_driver(spec_["traffic"]["driver"])
    record = driver.run(spec_, seed, seconds, trace, started)
    record["chips"] = spec_["cell"]["chips"]
    record["peaks"] = spec.peaks(device["kind"])
    checks = record["checks"]
    device = dict(device, memory_peak_bytes=record["peak_bytes"])

    result = {"correct": checks.ok, "attempted": record["attempted"],
              "failed": record["failed"]}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": record["end_to_end"][m["name"]],
                        "unit": m["unit"]}
            for m in spec_["end_to_end"]
            if m["name"] in record["end_to_end"]}
    else:
        events = tr.load(record["trace_path"]) if record.get(
            "trace_path") else None
        metrics = {}
        for m in spec_["per_layer"]:
            value = spec.load_reader(m["reader"])(record, events,
                                                  m.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["end_to_end_while_traced"] = record["end_to_end"]
        if events is not None:
            busy, window = tr.busy_and_window(events)
            device.update(busy_s=busy, window_s=window)
            result["breakdown"] = tr.breakdown(events)
        shutil.rmtree(common.TRACE_DIR, ignore_errors=True)
    # not read by the driver: where set-up went and how often it found
    # the compile cache; the window's three longest steps and the
    # longest the host spent in each phase of a turn, so that a run that
    # reads far off shows whether one stall or many slow steps did it,
    # and where
    for extra in ("setup_marks", "slowest_ms", "longest_ms"):
        if extra in record:
            result[extra] = record[extra]
    result["device"] = device
    result["not_compared"] = checks.notes
    result["checks"] = checks.as_dict()      # last: the numbers compared
    return result


def main(argv=None) -> int:
    args = _parse(argv)
    from . import spec
    spec_ = spec.load_cell(args.workload)

    from paddle_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    import jax
    # small programs are worth caching too: a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    chips = spec_["cell"]["chips"]
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"chipbench: {args.workload} needs {chips} chip(s), JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    spec.peaks(device["kind"])      # an unknown chip is an error, now

    result = measure(spec_, args.seed, args.seconds, bool(args.trace),
                     device, _STARTED)
    lines = "\n".join(
        f"check {k}: {v['value']:.6g} limit {v['limit']:.6g} "
        f"{'ok' if v['ok'] else 'FAILED'}"
        for k, v in result["checks"].items())
    print(json.dumps(result), flush=True)
    print(lines, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
