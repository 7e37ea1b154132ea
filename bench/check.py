"""The comparison that decides ``correct`` for served tokens.

The sampled requests' contexts and served tokens are scored by the plain
float32 reference that the configuration names
(``bench/reference/<reference>.py``, its ``score``), on weights
regenerated from the seed.  For each served token, the gap between the
log-probability the engine emitted with it and the reference's
log-probability of that token at that position is taken; the widest gap
over all of them is the number compared.  Tokens are sampled at the
configuration's temperature 1, where a logprob is the log-softmax of the
logits, so the gap checks the token's logit and the normalisation over the
whole vocabulary at once, for sampled as for greedy tokens.

With ``control=True`` the reference at the precision below the
configuration's (``fp8``) takes the program's place: its logprobs of the
same served tokens at the same positions are compared in place of the
engine's, and the verdict returned is the control's, with the program's own
under ``program``.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from bench import spec, weights


def _verdict(gaps: List[np.ndarray], limits: dict) -> Dict:
    lim = float(limits["logprob_gap_max"]["limit"])
    need = int(limits["tokens_compared"]["min"])
    finite = all(bool(np.isfinite(g).all()) for g in gaps)
    worst = max((float(np.abs(g).max()) for g in gaps), default=0.0)
    n = sum(len(g) for g in gaps)
    bad = sum(int(not np.isfinite(g).all() or np.abs(g).max() > lim)
              for g in gaps)
    checks = {
        "logprob_gap_max": {"value": worst, "limit": lim},
        "tokens_compared": {"value": n, "limit": need},
    }
    correct = finite and n >= need and worst <= lim and math.isfinite(worst)
    return {"correct": bool(correct), "checks": checks,
            "requests": len(gaps), "failed": bad}


def compare_served(c: dict, seed: int, sample: List[dict], limits: dict, *,
                   control: bool = False, root=spec.ROOT) -> Dict:
    ref_mod = spec.reference(c, root)
    w = weights.make(c, seed, root)
    prog, ctrl = [], []
    for s in sample:
        toks, k = s["tokens"], s["n_before"]
        lp_ref, _ = ref_mod.score(w, c, toks)
        ref = lp_ref[k - 1: len(toks) - 1]
        prog.append(np.asarray(s["lps"], np.float64) - ref)
        if control:
            lp_c, _ = ref_mod.score(w, c, toks, precision="fp8")
            ctrl.append(np.asarray(lp_c[k - 1: len(toks) - 1], np.float64) - ref)
    out = _verdict(prog, limits)
    if control:
        return dict(_verdict(ctrl, limits), program=out)
    return out
