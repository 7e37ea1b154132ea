"""Every per-layer metric reads a stored record of a traced run of each
cell's configuration to the value it read before the harness learned other
layouts (the numbers below were read by the readers as they stood then)."""

from __future__ import annotations

import pytest

from bench import modelcfg, spec

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}      # TPU v5 lite


def stored_record(config: str) -> dict:
    """A traced stretch of 12 steps: 2-9 decode rows of 4-9k tokens, a
    256-token prefill chunk every other step (one in three ends its
    context), one device with whole and cut programs and both kernels."""
    steps = []
    for i in range(12):
        rows = 2 + (5 * i) % 8
        steps.append({
            "decode_ctx": [4000 + 397 * i + 611 * j for j in range(rows)],
            "prefill_rows": ([[1536 + 256 * i, 256, i % 3 == 2]]
                             + ([[0, 61 + i, True]] if i % 4 == 1 else [])
                             if i % 2 else []),
            "pages_used": 2400 + 37 * i,
            "pages_committed": 4100 - 3 * i,
        })
    programs = ([{"kind": "decode", "whole": True, "seconds": 0.0731 + 0.0007 * i}
                 for i in range(12)]
                + [{"kind": "prefill", "whole": True, "seconds": 0.1052 + 0.0011 * i}
                   for i in range(6)]
                + [{"kind": "decode", "whole": False, "seconds": 0.021},
                   {"kind": "other", "whole": True, "seconds": 0.0004}])
    device = {"busy_s": 2.891, "programs": programs,
              "kernel_s": {"paged_decode_attention": 0.0600,
                           "paged_prefill_attention": 0.7795},
              "kernel_calls": {"paged_decode_attention": 96,
                               "paged_prefill_attention": 48},
              "top_ops": [], "idle_gaps": []}
    return {"config": modelcfg.load(spec.BENCH / "configs" / f"{config}.json"),
            "trace": {"window_s": 3.0271, "devices": [device]},
            "steps": steps, "pool_itemsize": 4, "act_itemsize": 2,
            "peak": PEAK, "chips": 1}


# read from stored_record() by the readers of the parent harness
BEFORE = {
    "qwen2-7b-7L": {
        "decode_batch_mean": 5.5, "kv_used_share": 63.764924075894534,
        "decode_dispatch_ms": 76.95, "prefill_dispatch_ms": 107.95,
        "paged_decode_roofline": 35.22920087912088,
        "paged_prefill_roofline": 0.3676475539090202,
        "rollout_mfu": 1.0895045676612973,
        "idle_share.rollout": 4.496052327309963},
    "qwen3-8b-8L": {
        "decode_batch_mean": 5.5, "kv_used_share": 63.764924075894534,
        "decode_dispatch_ms": 76.95, "prefill_dispatch_ms": 107.95,
        "paged_decode_roofline": 70.44519853479854,
        "paged_prefill_roofline": 0.42016863303888025,
        "rollout_mfu": 1.06641045999396,
        "idle_share.rollout": 4.496052327309963},
}


@pytest.mark.parametrize("config,metric", [
    (c, m) for c in sorted(BEFORE) for m in sorted(BEFORE[c])])
def test_metric_reads_the_stored_record_as_before(config, metric):
    read = spec.load_module(spec.BENCH / "metrics" / f"{metric}.py").read
    assert read(stored_record(config)) == BEFORE[config][metric]
