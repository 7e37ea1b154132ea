"""Run one benchmark cell and print its result as the last line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything it
needs is found by name (``bench/spec.py``).  It runs on the machine it is
started on, and only on a TPU with at least the chips the cell asks for.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
a stretch of the window and reports its per-layer metrics, with the
device's busy and window seconds and a breakdown.  Either way the served
output is compared with the plain reference after the window: the numbers
compared are printed beside their limits as the last lines of standard
error, and under ``checks``, the last key of the result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def device_or_exit(chips: int):
    """The chips to use; exits without a result unless JAX finds a TPU
    with at least ``chips`` devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: needs a TPU; JAX found {devs[0].platform}")
        sys.exit(3)
    if len(devs) < chips:
        log(f"bench: cell needs {chips} chips; JAX found {len(devs)}")
        sys.exit(3)
    return devs[:chips]


def result_line(cell: dict, out: dict, devs, trace: bool) -> dict:
    """The contract's result object from a window's output."""
    chk = out["check"]
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
    res = {"correct": bool(chk["correct"]), "attempted": int(out["attempted"]),
           "failed": int(chk["failed"])}
    if trace:
        from bench import spec

        rec = out["record"]
        tr = rec["trace"]
        ds = tr.get("devices", [])
        res["metrics"] = spec.read_per_layer(cell, rec)
        if ds:
            device["busy_s"] = sum(d["busy_s"] for d in ds) / len(ds)
            device["window_s"] = tr["window_s"]
            res["breakdown"] = {"device_ops": [list(x) for x in ds[0]["top_ops"]],
                                "idle_gaps": [list(x) for x in ds[0]["idle_gaps"]]}
    else:
        res["metrics"] = {}
        for m in cell["end_to_end"]:
            v = out.get(m["name"])
            if v is not None:
                res["metrics"][m["name"]] = {"value": float(v), "unit": m["unit"]}
    res["device"] = device
    res["checks"] = chk["checks"]
    return res


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(root / "src"), str(root)]
    from bench import spec

    cell = spec.cell(spec.load(root), args.workload, root)
    devs = device_or_exit(cell["chips"])
    from repro.launch.compile_cache import ENV_VAR, setup_compile_cache

    # the compile cache lives at one fixed path inside the checkout
    os.environ[ENV_VAR] = str(root / ".jax_cache")
    cache_dir = setup_compile_cache()
    log(f"bench: {args.workload} seed {args.seed} on {devs[0].device_kind} "
        f"x{len(devs)}; compile cache {cache_dir}")
    window = spec.load_module(cell["window"])
    out = window.run_cell(cell, args.seed, args.seconds, T_PROCESS,
                          trace=bool(args.trace))
    res = result_line(cell, out, devs, bool(args.trace))
    for k, v in out.get("notes", {}).items():
        log(f"bench: {k}: {v}")
    for k, v in res["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
