"""``bench/weights.py`` gives the configurations of the benchmark's cells
the trees it gave them when their layout was written into it, now that
the layout comes from each configuration's reference module: a digest of
every leaf, at the widths of ``tiny.py``, for two seeds."""

from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from bench import modelcfg, spec, weights

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 512}
# sha256 of each leaf's path, dtype, shape and bytes, in path order, as
# made before the layout moved into bench/reference/qwen_dense.py
DIGESTS = {
    ("qwen2-7b-7L", 3):
        "a8778950e7c790c3b118750bd2aae1b594dc100c241f8f6ae459847dbd7c3406",
    ("qwen2-7b-7L", 2**33 + 17):
        "05d1420204d77be5d31a3c7c299d522acd81810169e74e98da1b21a4c35832b2",
    ("qwen3-8b-8L", 3):
        "8d9f6dee101eb48635547b2faf826f6b9bf66cfe4837ad019f9c0e84a0557eed",
    ("qwen3-8b-8L", 2**33 + 17):
        "adc17e2b3c175fcdc5825018db9957f7c42b94abdcefca9b4f6615e4ede5df5d",
}


def digest(tree) -> str:
    h = hashlib.sha256()
    key = lambda kv: jax.tree_util.keystr(kv[0])
    for path, x in sorted(jax.tree_util.tree_leaves_with_path(tree), key=key):
        a = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config,seed", sorted(DIGESTS))
def test_weights_are_bit_identical(config, seed):
    c = {**modelcfg.load(spec.BENCH / "configs" / f"{config}.json"), **TINY}
    assert digest(weights.make(c, seed)) == DIGESTS[config, seed]
