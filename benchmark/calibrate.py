"""Reads, in one process, what the limits of a cell are set from: for each
seed the program's first steps against the plain reference (the lower
readings), and for the first `--controls` seeds the control (the reference in
fp8, put in the program's place) and the faults a training cell can have,
planted in the reference.  No measured window: training's readings need none.

    python3 benchmark/calibrate.py --workload nmt-train --seeds 11,12,13 --controls 3

`--program 0` leaves the program out and reads the control and the faults
alone, which are all the reference's: a four-chip cell's then need one chip.
One JSON line per seed on standard output and in chiprun_out/.
"""

import argparse
import gc
import json
import os
import sys
import time

import run


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--program", type=int, default=1)
    p.add_argument("--rehearsal", type=int, default=0)
    args = p.parse_args(argv)
    bench, cell, cfg, mix, limits = run.load_cell(args.workload, bool(args.rehearsal))

    import jax

    run.place_compile_cache(jax)
    import refsteps

    out_dir = os.path.join(run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    faults = ["half_batch"] + (["no_exchange"] if cell["chips"] > 1 else [])
    with open(os.path.join(out_dir, f"calibrate_{args.workload}.jsonl"), "a") as log:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            ref, t_seed = None, time.perf_counter()
            line = {"workload": args.workload, "seed": seed}
            if args.program:
                got = run.program_readings(cell, cfg, mix, seed)
                gc.collect()
                ref = run.reference_readings(cell, cfg, mix, seed)
                numbers, notes = refsteps.compare(got, ref)
                line.update(program=numbers, notes=notes, program_losses=got["losses"],
                            reference_losses=ref["losses"])
            elif i < args.controls:
                ref = run.reference_readings(cell, cfg, mix, seed)
            if i < args.controls:
                ctl = run.reference_readings(cell, cfg, mix, seed, precision="fp8")
                line["control_fp8"] = refsteps.compare(ctl, ref)[0]
                for fault in faults:
                    bad = run.reference_readings(cell, cfg, mix, seed, fault=fault)
                    line[fault] = refsteps.compare(bad, ref)[0]
            line["seconds"] = time.perf_counter() - t_seed
            text = json.dumps(line)
            print(text, flush=True)
            log.write(text + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
