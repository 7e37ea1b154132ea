"""The traced run on the CPU at a tiny size (device figures are absent
there, so the readers that need the chip return nothing), and the control:
the plain reference at float8 in the program's place comes out not correct
at the limits of the real cells."""

from __future__ import annotations

import json
import time

import jax

from bench import peaks, spec
from bench import run as runmod
from bench.tests import tiny


def test_traced_run_reads_metrics_by_name(tmp_path, capsys, monkeypatch):
    root = tiny.make_root(tmp_path, 0.25)
    monkeypatch.setattr(runmod, "device_or_exit",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(peaks, "for_kind",
                        lambda kind: {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    rc = runmod.main(["--workload", tiny.CELL, "--seed", "5", "--seconds", "2",
                      "--trace", "1"], root=root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    # the reader added as a new file is found by its name
    assert res["metrics"]["steps_traced"]["value"] > 0
    assert res["metrics"]["decode_batch_mean"]["value"] > 0
    assert "rollout_tokens_per_s" not in res["metrics"]


def test_control_fails_the_cells_limits(tmp_path):
    """The float8 reference in the program's place, through the harness's
    own verdict and result line: not correct, at every real cell's limit."""
    real = spec.load()
    limit = max(spec.cell(real, w["name"])["limits"]["logprob_gap_max"]["limit"]
                for w in real["workloads"])
    root = tiny.make_root(tmp_path, limit)
    cell = spec.cell(spec.load(root), tiny.CELL, root)
    window = spec.load_module(cell["window"])
    out = window.run_cell(cell, 11, 2.0, time.perf_counter(), control=True)
    res = runmod.result_line(cell, out, jax.devices()[:1], False)
    assert res["correct"] is False
    assert res["checks"]["logprob_gap_max"]["value"] > limit
    # the program itself, on the same tokens, passes the tightest limit
    prog = out["check"]["program"]
    assert prog["correct"] is True
    assert prog["checks"]["logprob_gap_max"]["value"] < min(
        spec.cell(real, w["name"])["limits"]["logprob_gap_max"]["limit"]
        for w in real["workloads"])
