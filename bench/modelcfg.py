"""A configuration file of the benchmark -> the program's ``ModelConfig``.

A file under ``bench/configs/`` holds the model as it is run, under the
keys of the model's published ``config.json``, and names the entry of the
program's registry (``registered``) that implements the architecture.
Every published key that maps to a field of the registry's config is
compared with it; a difference is an error unless the file lists the key
under ``overrides`` (with its reason) or under ``reduced``, and the file's
value then runs.  A file may map further keys under ``"fields": {published
key: ModelConfig field}``.  Keys with no single field (the per-layer
``layer_types`` and ``mlp_layer_types``, cut to ``num_hidden_layers``, and
the shared experts' width) are compared with what the resulting config
runs, and may not differ.  A key of ``SHAPE_KEYS`` that the file states and
nothing maps is an error unless listed under ``overrides``.  So the
numbers in the file are the numbers that run.
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
    "num_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "d_ff_expert",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_k_dense",
    "first_k_dense": "first_k_dense",
    "num_dense_layers": "first_k_dense",
    "sliding_window": "window",
}
# published keys that shape the model: each one a file states has to be
# compared (mapped, here or under "fields", or derived) or declared
SHAPE_KEYS = (
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "n_shared_experts",
    "first_k_dense_replace", "first_k_dense", "num_dense_layers",
    "norm_topk_prob",
    "sliding_window", "layer_types", "mlp_layer_types",
    "rope_scaling", "rope_parameters", "partial_rotary_factor")
# published keys that no one field holds, compared with what the config runs
DERIVED_KEYS = ("layer_types", "mlp_layer_types",
                "shared_expert_intermediate_size")
# the program's names of layer kinds under the published ones
LAYER_TYPES = {"global": "full_attention", "local": "sliding_attention"}
MLP_LAYER_TYPES = {"dense": "dense", "moe": "sparse"}
# what the program hard-codes for every dense model (models/layers.py)
PROGRAM_RMS_EPS = 1e-6


class ConfigError(ValueError):
    pass


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def _derived(cfg) -> dict:
    """What ``cfg`` runs, under the published keys that no one field holds
    (``DERIVED_KEYS``)."""
    L = cfg.n_layers
    return {
        "layer_types": [LAYER_TYPES.get(m, m) for m in cfg.layer_mixers()],
        "mlp_layer_types": [MLP_LAYER_TYPES.get(k, k) for k in
                            map(cfg.mlp_kind_for_layer, range(L))],
        "shared_expert_intermediate_size": cfg.n_shared_experts * cfg.d_ff_expert,
    }


def to_model_config(c: dict):
    """The program's ModelConfig for configuration file contents ``c``."""
    from repro.configs import get_config

    base = get_config(c["registered"])
    fields = {**HF_TO_FIELD, **c.get("fields", {})}
    known = {f.name for f in dataclasses.fields(base)}
    for key, field in fields.items():
        if field not in known:
            raise ConfigError(f"{c['name']}: fields maps {key!r} to {field!r}, "
                              f"which the program's ModelConfig does not have")
    allowed = set(c.get("reduced", [])) | {o["key"] for o in c.get("overrides", [])}
    want = {k: c[k] for k in fields if c.get(k) is not None}
    want.setdefault("head_dim", head_dim(c))
    if c.get("use_sliding_window") is False:     # the key is then not in effect
        want.pop("sliding_window", None)
    for key in SHAPE_KEYS:
        if (c.get(key) is not None and key not in fields
                and key not in DERIVED_KEYS and key not in allowed):
            raise ConfigError(
                f"{c['name']}: the file states {key}={c[key]!r}, which maps to "
                f"no field of the program's ModelConfig; map it under fields "
                f"or list it under overrides")
    changes = {}
    for key, val in want.items():
        field = fields[key]
        have = getattr(base, field)
        if isinstance(have, tuple) and isinstance(val, list):
            val = tuple(val)
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
    cfg = dataclasses.replace(base, name=c["name"], **changes)
    for key, runs in _derived(cfg).items():
        if c.get(key) is None or key in fields:
            continue
        stated = c[key]
        if isinstance(stated, list):
            stated = stated[:cfg.n_layers]
        if stated != runs:
            raise ConfigError(f"{c['name']}: the file states {key}={stated!r} "
                              f"but the program runs {runs!r}")
    return cfg


def engine_kwargs(c: dict, pool_pages: int) -> dict:
    """``InferenceEngine`` keyword arguments of configuration ``c`` with a
    KV pool made at its cap of ``pool_pages`` pages: what the rollout window
    runs and ``bench/tools/size_pool.py`` sizes."""
    e = c["engine"]
    return {"max_batch": int(e["max_batch"]), "temperature": float(e["temperature"]),
            "slab_len": int(pool_pages), "max_pool_pages": int(pool_pages)}
