"""Model FLOPs of the tokens the traced stretch processed (prefill and
decode, at true context lengths, nothing padded) per second of the
stretch, over the chips' bf16 peak, in % (whole rollout step)."""
from bench import flops


def read(record):
    c, steps, tr = record["config"], record["steps"], record["trace"]
    if not steps or not tr:
        return None
    tokens = pairs = logits = 0.0
    for s in steps:
        tokens += len(s["decode_ctx"])
        pairs += sum(s["decode_ctx"])
        logits += len(s["decode_ctx"])
        for off, take, last in s["prefill_rows"]:
            tokens += take
            pairs += take * off + take * (take + 1) / 2.0
            logits += int(last)
    work = flops.model_flops(c, tokens=tokens, attn_pairs=pairs, logit_rows=logits)
    peak = record["peak"]["bf16_flops"] * record["chips"]
    return 100.0 * work / tr["window_s"] / peak
