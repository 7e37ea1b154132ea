"""Model FLOPs of the tokens the traced stretch processed (prefill and
decode, at true context lengths, nothing padded; ``flops.step_flops``,
layer by layer) per second of the stretch, over the chips' bf16 peak, in %
(whole rollout step)."""
from bench import flops


def read(record):
    steps, tr = record["steps"], record["trace"]
    if not steps or not tr:
        return None
    work = flops.step_flops(record["config"], steps)
    peak = record["peak"]["bf16_flops"] * record["chips"]
    return 100.0 * work / tr["window_s"] / peak
