#!/usr/bin/env python3
"""Readings of the numbers compared, for setting their limits: the
program, its bf16 control or the program with a planted fault, each run
through the cell's set-up (the steps the reference follows) on many
seeds in one process, then compared with the reference. No window is
measured. One JSON line per seed.

    python3 bench/tools/calibrate.py --workload mlp.settled \\
        --variant program --seeds 101 102 103
    python3 bench/tools/calibrate.py --workload mlp.settled \\
        --variant control --seeds 201 202 203

``control`` runs the program with the configuration's ``dtype`` set to
bfloat16 (the program's own lower-precision path); ``half_batch`` plants
the fault of ``faults.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))


def readings(cell, seed: int, variant: str) -> dict:
    from bench.lib import harness
    from bench.tools import faults
    training = {"dtype": "bfloat16"} if variant == "control" else None
    ctx = (faults.FAULTS[variant]() if variant in faults.FAULTS
           else contextlib.nullcontext())
    t = time.perf_counter()
    with ctx:
        prog = harness.Program(cell, seed, training)
        drv = cell.driver(prog, cell.traffic)
    setup = time.perf_counter() - t
    handoff, before = drv.handoff, drv.rounds_before
    omega0, ecfg = prog.omega0, prog.ecfg
    drv.release()
    del drv, prog
    gc.collect()
    t = time.perf_counter()
    numbers = harness.reference_numbers(cell, seed, handoff, before, omega0,
                                        ecfg)
    return {"seed": seed, "variant": variant, "rounds": before,
            "setup_s": setup, "reference_s": time.perf_counter() - t,
            "numbers": numbers}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", default="program",
                    choices=("program", "control", "half_batch",
                             "unchanged"))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from bench.lib import jaxcache
    jaxcache.enable()
    from bench.lib import harness
    cell = harness.Cell(ROOT, args.workload)
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.variant)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
