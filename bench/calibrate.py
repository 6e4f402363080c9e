"""Readings that a serving cell's correctness limit is set from, on the chip.

    python bench/calibrate.py --workload NAME --seeds 1 2 3 ...

For each seed, in one process: the cell's set-up and one wave of requests
as a run's window makes it, then two readings over the same sampled requests
as the run's check takes: the program's widest logit gap (what a sound run
reads) and the control's (the float32 reference at float8 put in the
program's place: at each position the token float8 ranks first, measured
against the float32 reference's best).  The limit goes between the largest
program reading and the smallest control reading.  Without a TPU it exits
non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run
    spec = run.cell_spec(args.workload)
    run.device_check(spec["cell"]["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    Driver = run.load_module(
        ROOT / "bench" / "drivers" / f"{spec['mix']['driver']}.py").Driver
    rows = []
    for seed in args.seeds:
        t0 = time.time()
        d = Driver(spec["config"], spec["mix"], seed,
                   chips=spec["cell"]["chips"])
        d.setup()
        win = d.window(0.0)
        d.release()
        picked = d.sample(win)
        prog = d.logit_gaps(picked)
        ctrl = d.logit_gaps(picked, precision="fp8", chosen="reference")
        row = {"seed": seed, "program": float(prog.max()),
               "program_flips": float((prog > 0).mean()),
               "control": float(ctrl.max()),
               "control_flips": float((ctrl > 0).mean()),
               "tokens": int(prog.size), "secs": time.time() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows),
        "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
