"""Random weights from ``--seed``, made on the device in one jitted call,
in the dtype they are served in (the configuration's ``torch_dtype``) and
in the parameter layout the program consumes.

The layout is the program's interface (``repro.models.init_params``), and
the configuration's reference module states it: its ``shapes(c)`` maps
each leaf's path to (shape, kind, fan-in), with the leaves of scanned
groups carrying the group index first.  ``check_layout`` holds it to what
the program would initialise.  The values are this file's own: leaves are
drawn in sorted path order, each from the seed's key folded with its index;
norm weights and biases are drawn away from their identity values, so that
the comparison with the reference sees every term of the layer (the
program initialises them to zero).  RMSNorm weights are stored as
``scale`` with weight ``1 + scale``, as the program applies them.

Kinds: ``"w"`` a weight, N(0, 1 / fan-in) in the served dtype;
``"router"`` the same, kept in float32 as the program keeps routers;
``"bias"`` N(0, BIAS_STD) in the served dtype; ``"norm"`` N(0, NORM_STD)
in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

NORM_STD = 0.1      # spread of RMSNorm weights around 1
BIAS_STD = 0.1      # spread of QKV biases
EOS_ID = 2          # its output weights are zero, so its logit is always 0


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A raw threefry key for any whole ``seed`` (64 bits are kept)."""
    s = int(seed) % 2**64
    hi, lo = s >> 32, s & 0xFFFFFFFF
    key = jnp.asarray(np.array([hi, lo], np.uint32))
    return jax.random.fold_in(key, stream) if stream else key


def _nest(flat: dict) -> dict:
    tree = {"prefix": {}, "suffix": {}}
    for path, v in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


def make(c: dict, seed: int, root=spec.ROOT) -> dict:
    """The parameter tree for config ``c``, on the default device, in the
    layout of the reference module that ``c`` names under ``root``."""
    dtype = jnp.dtype(c["torch_dtype"])
    leaves = spec.reference(c, root).shapes(c)

    def build(key):
        out = {}
        for i, (path, (shape, kind, fan_in)) in enumerate(sorted(leaves.items())):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, jnp.float32)
            if kind == "w":
                out[path] = (x / np.sqrt(fan_in)).astype(dtype)
            elif kind == "router":
                out[path] = x / np.sqrt(fan_in)
            elif kind == "bias":
                out[path] = (x * BIAS_STD).astype(dtype)
            elif kind == "norm":        # norms stay f32, as the program keeps them
                out[path] = x * NORM_STD
            else:
                raise ValueError(f"{path}: unknown leaf kind {kind!r}")
        # a random model would end some responses early by sampling EOS, at
        # a rate set by the seed's weights; with a zero logit among N(0, 1)
        # ones it ends about one in 10^5 tokens, and the traffic's drawn
        # lengths decide the work
        if "lm_head" in out:
            out["lm_head"] = out["lm_head"].at[:, EOS_ID].set(0)
        else:
            out["embed"] = out["embed"].at[EOS_ID].set(0)
        return _nest(out)

    return jax.jit(build)(seed_key(seed))


def check_layout(params, cfg) -> None:
    """Raise if the tree differs from what the program would initialise."""
    from repro.models import init_params

    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    sig = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    a, b = sig(params), sig(want)
    if jax.tree.structure(a) != jax.tree.structure(b) or a != b:
        raise ValueError(f"bench weights do not match the program's layout:\n"
                         f"bench {a}\nprogram {b}")
