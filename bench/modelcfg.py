"""A configuration file of the benchmark -> the program's ``ModelConfig``.

A file under ``bench/configs/`` holds the model as it is run, under the
keys of the model's published ``config.json``, and names the entry of the
program's registry (``registered``) that implements the architecture.
Every published key that the registry also states is compared with it; a
difference is an error unless the file lists the key under ``overrides``
(with its reason) or under ``reduced``.  So the numbers in the file are
the numbers that run.
"""

from __future__ import annotations

import dataclasses
import json

# published config.json key -> ModelConfig field
HF_TO_FIELD = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
    "qk_norm": "qk_norm",
    "torch_dtype": "dtype",
}
# what the program hard-codes for every dense model (models/layers.py)
PROGRAM_RMS_EPS = 1e-6


class ConfigError(ValueError):
    pass


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def to_model_config(c: dict):
    """The program's ModelConfig for configuration file contents ``c``."""
    from repro.configs import get_config

    base = get_config(c["registered"])
    allowed = set(c.get("reduced", [])) | {o["key"] for o in c.get("overrides", [])}
    want = {k: c[k] for k in HF_TO_FIELD if k in c}
    want.setdefault("head_dim", head_dim(c))
    changes = {}
    for key, val in want.items():
        field = HF_TO_FIELD[key]
        have = getattr(base, field)
        if have != val:
            if key not in allowed:
                raise ConfigError(
                    f"{c['name']}: registry {c['registered']!r} has {field}={have!r}"
                    f" but the file states {key}={val!r}; list it under "
                    f"overrides or reduced")
            changes[field] = val
    if float(c.get("rms_norm_eps", PROGRAM_RMS_EPS)) != PROGRAM_RMS_EPS:
        raise ConfigError(f"{c['name']}: the program's RMSNorm eps is "
                          f"{PROGRAM_RMS_EPS}, the file states {c['rms_norm_eps']}")
    if c.get("hidden_act", "silu") != "silu":
        raise ConfigError(f"{c['name']}: the program's MLP is SwiGLU (silu)")
    return dataclasses.replace(base, name=c["name"], **changes)

