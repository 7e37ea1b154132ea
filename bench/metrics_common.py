"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``."""

from __future__ import annotations

from bench import flops
from bench.trace import KERNELS


def program_ms(record, kind):
    devs = (record.get("trace") or {}).get("devices", [])
    per = []
    for d in devs:
        xs = [p["seconds"] for p in d["programs"] if p["kind"] == kind and p["whole"]]
        if xs:
            per.append(1e3 * sum(xs) / len(xs))
    return sum(per) / len(per) if per else None


def idle_share(record):
    tr = record.get("trace") or {}
    devs = tr.get("devices", [])
    if not devs or not tr.get("window_s"):
        return None
    busy = sum(d["busy_s"] for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / tr["window_s"])


def _work(record, kind):
    """Mean (flops, bytes) of one kernel call (one full-attention layer of
    one step)."""
    c = record["config"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or c["hidden_size"] // H
    kw = dict(heads=H, kv_heads=K, head_dim=dh,
              pool_itemsize=record["pool_itemsize"],
              act_itemsize=record["act_itemsize"])
    calls = []
    for s in record["steps"]:
        if kind == "decode" and s["decode_ctx"]:
            calls.append(flops.decode_attention(s["decode_ctx"], **kw))
        if kind == "prefill" and s["prefill_rows"]:
            calls.append(flops.prefill_attention(
                [(o, t) for o, t, _ in s["prefill_rows"]], **kw))
    if not calls:
        return None
    return (sum(f for f, _ in calls) / len(calls),
            sum(b for _, b in calls) / len(calls))


def kernel_roofline(record, kind):
    devs = (record.get("trace") or {}).get("devices", [])
    name = KERNELS[kind]
    times = [d["kernel_s"][name] / d["kernel_calls"][name]
             for d in devs if d["kernel_calls"].get(name)]
    work = _work(record, kind) if times else None
    if work is None:
        return None
    per_call = sum(times) / len(times)
    return flops.roofline_share(work[0], work[1], per_call, record["peak"])
