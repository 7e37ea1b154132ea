"""Every cell resolves its files by name, configurations are what runs,
and adding a cell takes new files and entries only."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from bench import check, modelcfg, spec, weights
from bench.tests import tiny


def test_every_workload_resolves():
    bm = spec.load()
    assert bm["command"] == ["python3", "bench/run.py"]
    for w in bm["workloads"]:
        c = spec.cell(bm, w["name"])
        assert c["window"].is_file()
        assert c["limits"]["logprob_gap_max"]["limit"] > 0
        for m in c["per_layer"]:
            assert c["metric_files"][m["name"]].is_file()
            assert hasattr(spec.load_module(c["metric_files"][m["name"]]), "read")
        assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
        assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("name", ["qwen2-7b-7L", "qwen3-8b-8L"])
def test_config_is_what_runs(name):
    bm = spec.load()
    entry = {c["name"]: c for c in bm["configs"]}[name]
    c = modelcfg.load(spec.ROOT / entry["file"])
    assert c["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    cfg = modelcfg.to_model_config(c)
    assert cfg.n_layers == c["num_hidden_layers"]
    assert cfg.d_model == c["hidden_size"] and cfg.d_ff == c["intermediate_size"]
    assert cfg.tie_embeddings is False
    shapes = jax.eval_shape(lambda: weights.make(c, 2**33 + 1))
    weights.check_layout(shapes, cfg)


def test_registry_difference_must_be_declared():
    c = dict(modelcfg.load(spec.ROOT / "bench/configs/qwen3-8b-8L.json"))
    c["overrides"] = []
    with pytest.raises(modelcfg.ConfigError, match="tie_embeddings"):
        modelcfg.to_model_config(c)


def test_new_cell_takes_only_new_files(tmp_path):
    root = tiny.make_root(tmp_path, 0.25)
    bm = spec.load(root)
    c = spec.cell(bm, tiny.CELL, root)
    assert c["config"]["name"] == "tiny-qwen3"
    assert c["traffic"]["group_size"] == 2
    assert c["window"].name == "tiny_window.py"
    assert hasattr(spec.load_module(c["window"]), "run_cell")
    record = {"steps": [{"decode_ctx": [3, 4], "prefill_rows": [],
                         "pages_used": 2, "pages_committed": 4}],
              "trace": {}, "config": c["config"]}
    got = spec.read_per_layer(c, record)
    assert got["steps_traced"]["value"] == 1
    assert got["decode_batch_mean"]["value"] == 2
    assert got["kv_used_share"]["value"] == 50
    assert "paged_decode_roofline" not in got       # no trace: left out
    with pytest.raises(spec.SpecError):
        spec.cell(bm, "no-such-cell", root)
    # a configuration of another layout: routed experts, two layers to a
    # scanned group, and a reference module of its own
    moe = spec.cell(bm, tiny.MOE_CELL, root)
    assert moe["reference"] == root / "bench" / "reference" / "tiny_moe.py"
    cfg = modelcfg.to_model_config(moe["config"])
    assert cfg.pattern == ("local", "global") and cfg.window == 16
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff_expert) == (8, 2, 32)
    params = weights.make(moe["config"], 2**33 + 5, root)
    weights.check_layout(params, cfg)
    assert sorted(params["groups"]) == ["sub0", "sub1"]
    assert params["groups"]["sub1"]["mlp"]["router"].dtype == jnp.float32
    sample = [{"tokens": [5, 6, 7, 8, 9], "n_before": 2, "lps": [-0.5] * 3}]
    res = check.compare_served(moe["config"], 2**33 + 5, sample, moe["limits"],
                               control=True, root=root)
    # the fixture's score: -0.5 a token at f32, -1.5 at fp8
    assert res["program"]["checks"]["logprob_gap_max"]["value"] == 0.0
    assert res["program"]["correct"] is True
    assert res["checks"]["logprob_gap_max"]["value"] == 1.0
    assert res["correct"] is False


@pytest.mark.parametrize("reference", [None, "no_such_reference"])
def test_reference_is_found_by_name(tmp_path, reference):
    root = tiny.make_root(tmp_path, 0.25)
    path = root / "bench" / "configs" / "tiny-moe.json"
    c = dict(tiny.MOE_CONFIG)
    c.pop("reference")
    if reference:
        c["reference"] = reference
    path.write_text(json.dumps(c))
    with pytest.raises(spec.SpecError, match="reference"):
        spec.cell(spec.load(root), tiny.MOE_CELL, root)


@pytest.mark.parametrize("change,match", [
    ({"overrides": [o for o in tiny.MOE_CONFIG["overrides"]
                    if o["key"] != "num_experts"]}, "n_experts"),
    ({"rope_parameters": {"rope_type": "yarn", "factor": 16}}, "rope_parameters"),
    ({"layer_types": ["full_attention"] * 6}, "layer_types"),
    ({"mlp_layer_types": ["dense"] + ["sparse"] * 5}, "mlp_layer_types"),
], ids=["expert-count", "rope-parameters", "layer-types", "mlp-layer-types"])
def test_undeclared_layout_difference_is_refused(change, match):
    with pytest.raises(modelcfg.ConfigError, match=match):
        modelcfg.to_model_config({**tiny.MOE_CONFIG, **change})
