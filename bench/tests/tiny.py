"""A throwaway cell at a size the CPU runs in seconds: a two-layer model at
Qwen3's layout, a short GRPO mix, a window kind and a per-layer metric,
written as new files beside a copy of the benchmark, the way a later
change adds a cell.  Beside it, a configuration of another layout (routed
experts, a sliding-window and a full layer to a scanned group) with its own
reference module (``tiny_moe_reference.py``) and a cell of its own, added
the same way."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny-qwen3", "registered": "qwen3-8b", "source": "test fixture",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2, "num_key_value_heads": 2,
    "head_dim": 16, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16", "vocab_size": 512,
    "qkv_bias": False, "qk_norm": True, "reduced": ["num_hidden_layers"],
    "overrides": [{"key": k, "value": None, "why": "test size"} for k in
                  ("head_dim", "hidden_size", "intermediate_size",
                   "num_attention_heads", "num_key_value_heads", "vocab_size",
                   "tie_word_embeddings")],
    "engine": {"max_batch": 8, "temperature": 1.0, "pool_pages": 60},
    "reference": "qwen_dense",
}
MIX = {
    "window": "tiny_window", "group_size": 2,
    "prompt_len": {"dist": "uniform", "lo": 20, "hi": 60, "strata": 4},
    "response_len": {"dist": "lognormal", "mean": 24, "sigma": 0.6, "cap": 64,
                     "strata": 4},
    "response_cap": 64, "check": {"requests": 2},
}
WINDOW = ('"""Test window kind: the rollout window under another name."""\n'
          'from bench.windows.rollout import run_cell  # noqa: F401\n')
METRIC = '"""Test reader."""\n\n\ndef read(record):\n    return len(record["steps"])\n'
CELL = "tiny-qwen3.rollout-tiny"

MOE_CONFIG = {
    "name": "tiny-moe", "registered": "qwen2-moe-a2.7b", "source": "test fixture",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 128,
    "num_experts": 8, "num_experts_per_tok": 2, "num_attention_heads": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "vocab_size": 512, "qkv_bias": True,
    "use_sliding_window": True, "sliding_window": 16,
    "layer_types": ["sliding_attention", "full_attention"] * 3,
    "mlp_layer_types": ["sparse"] * 6,
    "pattern": ["local", "global"], "fields": {"pattern": "pattern"},
    "reduced": ["num_hidden_layers"],
    "overrides": [{"key": k, "value": None, "why": "test size"} for k in
                  ("head_dim", "hidden_size", "intermediate_size",
                   "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                   "num_attention_heads", "num_key_value_heads", "vocab_size",
                   "sliding_window", "pattern")],
    "engine": {"max_batch": 8, "temperature": 1.0, "pool_pages": 60},
    "reference": "tiny_moe",
}
MOE_CELL = "tiny-moe.rollout-tiny"


def make_root(tmp: Path, limit: float) -> Path:
    """A copy of BENCHMARK.json and bench/ with the tiny cell added."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-qwen3.json").write_text(json.dumps(CONFIG))
    (root / "bench" / "traffic" / "rollout-tiny.json").write_text(json.dumps(MIX))
    (root / "bench" / "metrics" / "steps_traced.py").write_text(METRIC)
    (root / "bench" / "windows" / "tiny_window.py").write_text(WINDOW)
    (root / "bench" / "configs" / "tiny-moe.json").write_text(json.dumps(MOE_CONFIG))
    shutil.copy(Path(__file__).with_name("tiny_moe_reference.py"),
                root / "bench" / "reference" / "tiny_moe.py")
    for cell in (CELL, MOE_CELL):
        (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(
            {"logprob_gap_max": {"limit": limit}, "tokens_compared": {"min": 1}}))
    for name in ("tiny-qwen3", "tiny-moe"):
        bm["configs"].append({"name": name, "source": "test fixture",
                              "file": f"bench/configs/{name}.json",
                              "reduced": ["num_hidden_layers"], "why": "test"})
        bm["workloads"].append({"name": f"{name}.rollout-tiny", "config": name,
                                "traffic": "rollout-tiny", "chips": 1,
                                "why": "test"})
    bm["per_layer"].append({"name": "steps_traced", "unit": "steps",
                            "better": "higher", "source": "program_counter",
                            "layer": "engine scheduler",
                            "moves": "rollout_tokens_per_s",
                            "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "src").symlink_to(ROOT / "src")
    return root
