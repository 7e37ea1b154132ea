"""A whole run of a rollout cell on the CPU at a tiny size, past the
harness's look for a chip: a sound run comes out correct; a run whose
engine alters the tokens it produces, or whose steps leave the KV pool
unchanged, comes out not correct."""

from __future__ import annotations

import json

import jax

from bench import run as runmod
from bench.tests import tiny

SEED = 2**31 + 7


def _run(root, capsys, monkeypatch, trace=0):
    monkeypatch.setattr(runmod, "device_or_exit",
                        lambda chips: jax.devices()[:chips])
    rc = runmod.main(["--workload", tiny.CELL, "--seed", str(SEED),
                      "--seconds", "2", "--trace", str(trace)], root=root)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1])


def test_sound_run_is_correct(tmp_path, capsys, monkeypatch):
    rc, res = _run(tiny.make_root(tmp_path, 0.25), capsys, monkeypatch)
    assert rc == 0
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rollout_tokens_per_s", "token_gap_p95_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["logprob_gap_max"]["value"] < 0.25
    assert res["device"]["count"] == 1 and res["attempted"] > 0


def test_altered_token_is_caught(tmp_path, capsys, monkeypatch):
    from repro.serving.engine import InferenceEngine

    step = InferenceEngine.step

    def altered(self):
        events = step(self)
        for ev in events:                 # each token changed where produced
            ev.token = (ev.token + 1) % self.cfg.vocab_size
        return events

    monkeypatch.setattr(InferenceEngine, "step", altered)
    rc, res = _run(tiny.make_root(tmp_path, 0.25), capsys, monkeypatch)
    assert rc == 0
    assert res["correct"] is False
    assert res["checks"]["logprob_gap_max"]["value"] > 0.25


def test_pool_left_unchanged_is_caught(tmp_path, capsys, monkeypatch):
    from repro.models import attention
    from repro.serving import engine

    monkeypatch.setattr(engine, "_JIT_CACHE", {})     # retrace with the fault
    monkeypatch.setattr(attention, "paged_write", lambda pool, *a: pool)
    rc, res = _run(tiny.make_root(tmp_path, 0.25), capsys, monkeypatch)
    assert rc == 0
    assert res["correct"] is False
    assert res["checks"]["logprob_gap_max"]["value"] > 0.25
