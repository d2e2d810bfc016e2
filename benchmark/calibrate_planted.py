"""Reads a fault that a configuration's own reference can plant in itself
(`cfg["reference_fault"]`, named in that reference's docstring) against the
sound reference, seed by seed: the upper readings of a limit that
`calibrate.py`'s faults, which every training cell shares, do not give.

    python3 benchmark/calibrate_planted.py --workload nemotron-train-2k \\
        --fault no_routed_experts --seeds 11,12,13

One JSON line per seed on standard output and in chiprun_out/.
"""

import argparse
import json
import os
import sys

import run


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--fault", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--rehearsal", type=int, default=0)
    args = p.parse_args(argv)
    _, cell, cfg, mix, _ = run.load_cell(args.workload, bool(args.rehearsal))

    import jax

    run.place_compile_cache(jax)
    import refsteps

    out_dir = os.path.join(run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"calibrate_{args.workload}.jsonl"), "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            ref = run.reference_readings(cell, cfg, mix, seed)
            bad = run.reference_readings(cell, dict(cfg, reference_fault=args.fault), mix, seed)
            text = json.dumps({"workload": args.workload, "seed": seed,
                               args.fault: refsteps.compare(bad, ref)[0]})
            print(text, flush=True)
            log.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
