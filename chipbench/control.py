#!/usr/bin/env python3
"""The control of the correctness check: runs of a cell whose answers are
the program's rounded to bfloat16, as a program that kept its counts and
scores in bfloat16 (the step below the float32 the engines state) would
return them.  Every such run has to come out not correct.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Prints one JSON line per seed with the numbers compared.  The benchmark's
own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def bfloat16_answers(result):
    """Float parts of an answer rounded to bfloat16 and back."""
    import ml_dtypes

    if isinstance(result, tuple):
        return tuple(bfloat16_answers(r) for r in result)
    a = np.asarray(result)
    if a.dtype.kind != "f":
        return result
    return a.astype(ml_dtypes.bfloat16).astype(a.dtype)


def main(argv=None) -> int:
    from chipbench import run, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = spec.load()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    sys.path.insert(0, str(run.ROOT / "src"))
    for seed in args.seeds:
        r = run.run_cell(cell, cfg, mix,
                         spec.metrics_for(bench, cell["name"], False), seed,
                         args.seconds, False, hook=bfloat16_answers,
                         t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
