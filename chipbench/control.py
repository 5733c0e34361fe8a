"""Reads, on the chip, what the limits of `correct` are set from:

    python3 -m chipbench.control --workload W --seeds 1,2,3 --controls 3 \\
        --seconds S --out chiprun_out/control_W.jsonl

For each seed one short run of the cell as the benchmark makes it (the
program's readings: the lower end of a limit), and for the first
`--controls` seeds the driver's `control`: the reference in the
program's place one precision below, and the faults a reading has to
catch (the upper end), each held to the cell's committed limits as a
run is. One process, so that a compilation is paid once. Exits with 1
where a run of the program is not correct or a control or a fault comes
out as correct. No benchmark run calls this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    from . import spec
    spec_ = spec.load_cell(args.workload)
    from paddle_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chipbench.control: needs a TPU", file=sys.stderr)
        return 1
    driver = spec.load_driver(spec_["traffic"]["driver"])
    sound = True
    with open(args.out, "a") as out:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.time()
            record = driver.run(spec_, seed, args.seconds, False, t0)
            line = {"seed": seed, "correct": record["checks"].ok,
                    "program": {**record["checks"].notes,
                                **{k: v["value"] for k, v in
                                   record["checks"].as_dict().items()}},
                    "end_to_end": record["end_to_end"]}
            sound = sound and record["checks"].ok
            if i < args.controls:
                stand_ins = driver.control(spec_, record)
                line.update(stand_ins)
                sound = sound and not any(
                    s["ok"] for s in stand_ins.values())
            line["seconds"] = time.time() - t0
            del record
            gc.collect()    # an engine's cycles hold its KV pool till now
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
    print("chipbench.control: " + (
        "every program run correct, every control and fault not correct"
        if sound else "FAILED: a program run not correct, or a control "
        "or fault correct"), file=sys.stderr)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
