"""Fused multi-token decode horizon tests.

The engine decodes H tokens per dispatch inside one jitted scan with
on-device sampling and stopping.  Everything here checks the horizon
contract: H > 1 is bit-exact vs. H = 1 (tokens, logprobs, version spans)
under prefix sharing and migration; EOS / max_total stop rows mid-horizon;
page headroom is reserved up front (and survives pool growth); finished
rows park their device token buffer at the sentinel; steady-state decode
uploads nothing host->device; and block-table width jitter reuses wider
compiled closures instead of recompiling.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import tokenizer as tok
from repro.models import init_params
from repro.rl.sampler import request_key
from repro.serving.engine import (_JIT_CACHE, InferenceEngine,
                                  TOKEN_SENTINEL, _decode_family,
                                  _serve_pallas_default, jit_cache_stats)

_CFG = get_config("qwen2-7b").reduced(
    n_layers=2, n_heads=2, n_kv_heads=1, d_model=32, head_dim=16, d_ff=64,
    vocab_size=tok.VOCAB_SIZE, name="tiny-horizon")
_PARAMS = init_params(_CFG, jax.random.PRNGKey(0))


def _mk(horizon=1, temperature=1.0, **kw):
    eng_kw = dict(max_batch=4, slab_len=64, page_size=8,
                  temperature=temperature, horizon=horizon)
    eng_kw.update(kw)
    return InferenceEngine(_CFG, _PARAMS, **eng_kw)


def _run(eng, reqs, *, max_steps=200):
    """reqs: [(rid, prompt, max_total, key)] -> ({rid: [(tok, lp, ver)]})"""
    for rid, prompt, max_total, key in reqs:
        eng.add_request(rid, prompt, key, max_total, len(prompt))
    out = {rid: [] for rid, _, _, _ in reqs}
    done = set()
    for _ in range(max_steps):
        if len(done) == len(reqs):
            break
        for e in eng.step():
            out[e.req_id].append((e.token, e.logprob, e.weight_version))
            if e.finished:
                done.add(e.req_id)
    assert len(done) == len(reqs), "requests did not finish"
    return out


def _toks(stream):
    return [t for t, _, _ in stream]


# --------------------------------------------------------------------------- #
# bit-exactness vs. H = 1
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("horizon", [4, 16])
@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_horizon_bit_exact_vs_h1(horizon, temperature):
    """Same tokens and logprobs for concurrent requests whose lengths are
    NOT horizon-aligned (rows finish mid-horizon)."""
    p1, p2, p3 = (tok.encode(s) for s in ["12+34=", "7*8=", "9-4="])
    reqs = [(1, p1, len(p1) + 13, request_key(7, 1)),
            (2, p2, len(p2) + 6, request_key(7, 2)),
            (3, p3, len(p3) + 21, request_key(7, 3))]
    ref = _run(_mk(1, temperature), reqs)
    out = _run(_mk(horizon, temperature), reqs)
    for rid, _, max_total, _ in reqs:
        assert _toks(out[rid]) == _toks(ref[rid]), rid
        np.testing.assert_allclose([lp for _, lp, _ in out[rid]],
                                   [lp for _, lp, _ in ref[rid]], atol=1e-4)


def test_horizon_bit_exact_group_prefix_sharing():
    """A GRPO group under H = 8: shared prompt pages COW inside the batched
    horizon reservation; tokens match H = 1 and all pages are freed."""
    prompt = tok.encode("25*4=")
    members = [(i, request_key(3, i), len(prompt) + 3 * (i + 1))
               for i in range(3)]

    def run_group(H):
        eng = _mk(H, temperature=1.0, page_size=4)
        free0 = eng.alloc.n_free
        eng.add_group(members, prompt, len(prompt))
        out = {m[0]: [] for m in members}
        done = set()
        while len(done) < len(members):
            for e in eng.step():
                out[e.req_id].append(e.token)
                if e.finished:
                    done.add(e.req_id)
        assert eng.alloc.n_free == free0
        return out

    ref, out = run_group(1), run_group(8)
    for rid, _, max_total in members:
        assert out[rid] == ref[rid], rid
        assert len(out[rid]) == max_total - len(prompt)


def test_eos_and_max_total_mid_horizon():
    """Rows stopping at different offsets inside one horizon emit exactly
    their budget and nothing after; an EOS-terminated row stops early."""
    prompt = tok.encode("12+34=")
    H = 8
    # max_total offsets 2, 5, 7 all land strictly inside the first decode
    # horizon (first token comes from the prefill step)
    reqs = [(i, prompt, len(prompt) + off, request_key(11, i))
            for i, off in [(0, 2), (1, 5), (2, 7)]]
    out = _run(_mk(H), reqs)
    for (rid, _, max_total, _), off in zip(reqs, [2, 5, 7]):
        assert len(out[rid]) == off, rid
    # an EOS sampled before max_total ends the stream mid-horizon: scan
    # seeds until one such request is found (sampling is deterministic,
    # so the found case is stable)
    hit = None
    for rid in range(50):
        ref = _run(_mk(1), [(rid, prompt, len(prompt) + 40,
                             request_key(13, rid))])
        if ref[rid][-1][0] == tok.EOS and len(ref[rid]) < 40:
            hit = (rid, ref[rid])
            break
    assert hit is not None, "no EOS-terminated request found"
    rid, ref_stream = hit
    out = _run(_mk(H), [(rid, prompt, len(prompt) + 40,
                         request_key(13, rid))])
    assert _toks(out[rid]) == _toks(ref_stream)
    assert out[rid][-1][0] == tok.EOS


# --------------------------------------------------------------------------- #
# horizon boundaries: migration + weight swaps
# --------------------------------------------------------------------------- #
def test_migration_at_horizon_boundary_bit_exact():
    """Drop after k fused steps, continue on another H > 1 engine: the
    joined stream equals the uninterrupted H = 1 run."""
    prompt = tok.encode("9*8=")
    key = request_key(5, 21)
    max_total = len(prompt) + 19
    ref = _run(_mk(1), [(21, prompt, max_total, key)])

    engB = _mk(4)
    engB.add_request(21, prompt, key, max_total, len(prompt))
    part = []
    for _ in range(3):                      # prefill + 2 fused horizons
        for e in engB.step():
            part.append(e.token)
    assert len(part) == 1 + 2 * 4
    hist = engB.drop_request(21)
    assert hist == prompt + part

    engC = _mk(4)
    rest = _run(engC, [(21, hist, max_total, key)])
    assert part + _toks(rest[21]) == _toks(ref[21])


def test_swap_weights_at_horizon_boundary_version_spans():
    """A swap between step() calls applies at a horizon boundary, so
    weight_version is constant within each horizon — and the token stream
    matches H = 1 with the swap at the same token offset."""
    params2 = init_params(_CFG, jax.random.PRNGKey(9))
    prompt = tok.encode("7-9=")
    key = request_key(2, 4)
    H = 4
    max_total = len(prompt) + 1 + 2 * H     # prefill token + 2 horizons

    def run(H_, swap_after_steps):
        eng = _mk(H_)
        eng.add_request(4, prompt, key, max_total, len(prompt))
        stream, steps = [], 0
        while 4 in eng.active_request_ids():
            if steps == swap_after_steps:
                eng.swap_weights(params2, 1)
            stream.extend((e.token, e.weight_version) for e in eng.step())
            steps += 1
        return stream

    # H=4: swap after prefill + one horizon  <=>  H=1: after prefill + 4
    out = run(H, 2)
    ref = run(1, 5)
    assert out == ref
    versions = [v for _, v in out]
    assert versions == [0] * (1 + H) + [1] * H


# --------------------------------------------------------------------------- #
# allocator headroom + device residency
# --------------------------------------------------------------------------- #
def test_headroom_reservation_across_pool_growth():
    """The up-front horizon reservation grows the pool mid-run without
    perturbing the token stream (tiny pool, H spanning several pages)."""
    kw = dict(max_batch=2, slab_len=8, page_size=4)
    # a 30-token prompt nearly fills the initial 8-usable-page (32-token)
    # pool, so the first H=8 reservation must grow it — whatever the
    # sampled tokens, as long as the request decodes at all
    prompt = tok.encode("12345+67890=" * 2 + "12345")
    assert 28 < len(prompt) <= 32
    max_total = len(prompt) + 32
    # first key whose reference stream outlives its prefill token (an EOS
    # first token would end the request before any decode reservation)
    for rid in range(8, 40):
        key = request_key(1, rid)
        ref = _run(_mk(1, **kw), [(rid, prompt, max_total, key)])
        if len(ref[rid]) > 1:
            break
    assert len(ref[rid]) > 1
    eng = _mk(8, **kw)
    pages0 = eng.alloc.num_pages
    out = _run(eng, [(rid, prompt, max_total, key)])
    assert _toks(out[rid]) == _toks(ref[rid])
    assert eng.alloc.num_pages > pages0, "pool never grew"
    assert eng.alloc.n_free == eng.alloc.num_pages - 1


def test_finished_rows_park_at_sentinel():
    """A finished row's stale last token must not linger in the device
    token buffer (it would leak into a reused batch row)."""
    prompt = tok.encode("1+1=")
    eng = _mk(4)
    out = _run(eng, [(1, prompt, len(prompt) + 6, request_key(0, 1))])
    assert len(out[1]) == 6
    assert np.asarray(eng._dev_tokens).tolist() == [TOKEN_SENTINEL] * 4
    assert eng.tokens_buf.tolist() == [TOKEN_SENTINEL] * 4


def test_steady_state_decode_uploads_nothing():
    """Between admissions/completions/page-boundary crossings, the fused
    decode re-uses the device-resident state and block table: dispatch
    count rises, upload counters do not."""
    # page_size 64 => the whole response fits the prompt's first page, so
    # no mid-run table change can force a block-table rebuild
    eng = _mk(4, page_size=64, slab_len=64)
    prompt = tok.encode("12+34=")
    eng.add_request(1, prompt, request_key(0, 1), len(prompt) + 40,
                    len(prompt))
    eng.step()                              # prefill (marks state dirty)
    eng.step()                              # first fused decode (uploads)
    st0, bt0, d0 = eng.n_state_uploads, eng.n_bt_uploads, \
        eng.n_decode_dispatches
    for _ in range(4):
        evs = eng.step()
        assert evs and not any(e.finished for e in evs)
    assert eng.n_decode_dispatches == d0 + 4
    assert eng.n_state_uploads == st0, "steady-state re-uploaded state"
    assert eng.n_bt_uploads == bt0, "steady-state re-uploaded block table"


# --------------------------------------------------------------------------- #
# JIT compile churn
# --------------------------------------------------------------------------- #
def test_jit_cache_padded_width_reuse():
    """Block-table width shrinking below an already-compiled width must NOT
    compile a narrower closure — the wider one is padded up to."""
    temp = 0.7310001                        # unique closure family
    H = 2
    family = _decode_family(_CFG, temp, H, _serve_pallas_default())
    n_family = lambda: sum(1 for k in _JIT_CACHE if k[:-1] == family)
    assert n_family() == 0

    # long prompt: 18 tokens @ page_size 4 -> needed width 5+ -> compile 8
    long_prompt = [tok.BOS] + [5] * 17
    eng = _mk(H, temperature=temp, page_size=4)
    _run(eng, [(1, long_prompt, len(long_prompt) + 5, request_key(0, 1))])
    assert n_family() == 1
    widths = [k[-1] for k in _JIT_CACHE if k[:-1] == family]
    assert widths == [8]

    # short prompt: needed width 2 -> pads up to the compiled 8
    reuse0 = jit_cache_stats()["padded_reuse"]
    eng2 = _mk(H, temperature=temp, page_size=4)
    _run(eng2, [(2, tok.encode("1+1="), 10, request_key(0, 2))])
    assert n_family() == 1, "narrower width was recompiled"
    assert jit_cache_stats()["padded_reuse"] > reuse0
