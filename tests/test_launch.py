"""Entry-point plumbing: the compile-cache helper, the depth cut, the serving
request loop, and the trainer's sharded state init."""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.configs.base import depth_cut
from repro.data import tokenizer as tok
from repro.launch import compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import serve_requests
from repro.launch.train import init_sharded_state
from repro.models import init_params
from repro.rl import grpo
from repro.rl.sampler import request_key
from repro.serving.engine import InferenceEngine


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "from_env"])
def test_compile_cache_dir(monkeypatch, tmp_path, restore_cache_dir,
                           env_dir):
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(compile_cache.DEFAULT_DIR)
        # fixed, inside the checkout: <repo>/.jax_cache
        assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    assert compile_cache.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_depth_cut_keeps_published_widths():
    full = get_config("qwen3-8b")
    cut = depth_cut(full, 8)
    assert cut.n_layers == 8 and cut.n_groups == 8
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "dtype"):
        assert getattr(cut, f) == getattr(full, f)
    assert cut.name != full.name          # engine JIT caches key on name


def _tiny():
    cfg = get_config("qwen3-8b").reduced(vocab_size=tok.VOCAB_SIZE)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_serve_requests_group_and_import():
    """The request loop admits a GRPO group plus a single request, runs
    ``on_step`` at horizon boundaries, and serves a request imported into
    a second engine to the same tokens as the source."""
    cfg, params = _tiny()
    kw = dict(max_batch=4, slab_len=32, page_size=4, horizon=2,
              temperature=1.0, use_pallas=False)
    prompt = tok.encode("12+34=")
    n = len(prompt)
    groups = [([(0, request_key(0, 0), n + 10),
                (1, request_key(0, 1), n + 10)], prompt, n),
              ([(2, request_key(0, 2), n + 10)], prompt, n)]
    src = InferenceEngine(cfg, params, **kw)
    moved = {}

    def on_step(i, events):
        if i == 1 and not moved:
            rid = src.exportable_request_ids()[0]
            state = src.export_request_state([rid])
            dst = InferenceEngine(cfg, params, **kw)
            dst.import_request_state(state)
            req = state["requests"][0]
            moved.update(rid=rid, dst=dst,
                         cut=len(req["tokens"]) - req["n_prompt"])

    out = serve_requests(src, groups, on_step=on_step)
    assert sorted(out) == [0, 1, 2]
    assert all(evs[-1].finished for evs in out.values())
    rid, dst = moved["rid"], moved["dst"]
    resumed = serve_requests(dst, [])
    assert list(resumed) == [rid] and dst.n_prefill_tokens == 0
    tail = [(e.token, e.logprob) for e in out[rid][moved["cut"]:]]
    assert [(e.token, e.logprob) for e in resumed[rid]] == tail
    assert not src.active_request_ids() and not dst.active_request_ids()


def test_init_sharded_state_matches_unsharded_init():
    cfg, _ = _tiny()
    mesh = make_local_mesh(1, 1)
    key = jax.random.PRNGKey(3)
    state, sharding = init_sharded_state(cfg, key, mesh)
    want = grpo.init_train_state(init_params(cfg, key))
    assert jax.tree.structure(state) == jax.tree.structure(want)
    for a, b, s in zip(jax.tree.leaves(state), jax.tree.leaves(want),
                       jax.tree.leaves(sharding)):
        assert isinstance(s, NamedSharding) and a.sharding == s
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6)
