"""The control of a cell's correctness check, and the readings its limit is
set from.

  python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed, one run of the cell as the benchmark makes it (set-up, a
window of ``--seconds``), whose served tokens are then scored with the
reference at float8 e4m3 put in the program's place.  Prints, per seed, the
result line that this control makes (it has to read ``correct`` false, with
``logprob_gap_max`` above the cell's limit) and the program's own widest gap
on the same tokens.  Exits non-zero if any seed's control comes out correct.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run, spec
    from repro.launch.compile_cache import ENV_VAR, setup_compile_cache

    cell = spec.cell(spec.load(ROOT), args.workload, ROOT)
    os.environ[ENV_VAR] = str(ROOT / ".jax_cache")     # as bench/run.py
    setup_compile_cache()
    window = spec.load_module(cell["window"])
    devs = run.device_or_exit(cell["chips"])
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = window.run_cell(cell, seed, args.seconds, time.perf_counter(),
                              control=True)
        res = run.result_line(cell, out, devs, False)
        gap = res["checks"]["logprob_gap_max"]
        failed = not res["correct"] and gap["value"] > gap["limit"]
        bad += not failed
        print(json.dumps({"seed": seed, "control_correct": res["correct"],
                          "control_gap": gap["value"], "limit": gap["limit"],
                          "program_gap": out["check"]["program"]["checks"]
                          ["logprob_gap_max"]["value"],
                          "tokens": res["checks"]["tokens_compared"]["value"]}),
              flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
