"""Streamed token-level collection (paper technique 3) vs. batch collection.

The contract under test: the collection policy changes WHEN trainer-side
work happens, never WHAT is computed —

  * ``batch`` is the bit-identical legacy collector;
  * ``streamed`` consumes the per-token event stream, starts per-row work
    as rows finish, and credits the step's tail flush with the preprocess
    seconds already overlapped — yet produces the same completed-response
    set and (on the real backend) bit-identical final params, because
    crediting is restricted to post-rollout tail flushes (partition-safe)
    and the seeding controller sees trainer work, not critical-path time.
"""

import jax
import numpy as np
import pytest

from repro.core.faults import FaultPlan, check_invariants
from repro.core.hybrid_runtime import HybridRunner, RunnerConfig
from repro.core.microbatch import (BatchCollection, MicrobatchCollector,
                                   StreamedCollection, make_collection_policy)
from repro.core.perfmodel import ModelPerf
from repro.core.requests import Request
from repro.core.spot_trace import TraceEvent

PERF = ModelPerf(n_params=7e9, n_active=7e9)
TRACE = [TraceEvent(0.0, +4), TraceEvent(300.0, -1), TraceEvent(600.0, +2)]


def _mkcfg(seed, collection="batch", **kw):
    fp = FaultPlan(seed=seed, corrupt_p=0.02, prune_p=0.01, stall_p=0.02,
                   stall_s=2.0, hard_kill_fraction=0.5, grace_s=2.0,
                   trainer_stall_windows=((100.0, 50.0, 1.5),))
    return RunnerConfig(mode="rlboost", n_prompts=8, group_size=4,
                        mean_response=800, max_response=2048, m_b=8,
                        seed=seed, fault_plan=fp, collection=collection,
                        **kw)


def _run(cfg, n_steps=3):
    r = HybridRunner(cfg, PERF)
    r.load_trace(TRACE)
    r.run(n_steps=n_steps)
    return r


# --------------------------------------------------------------------------- #
# policy unit behavior
# --------------------------------------------------------------------------- #
def _row(rid, group, n_gen, completed_at=None):
    r = Request(id=rid, group=group, prompt_len=10, max_total=100, seed=0)
    r.n_generated = n_gen
    r.completed_at = completed_at
    return r


def test_factory_and_legacy_alias():
    p = make_collection_policy("batch", group_size=4, min_microbatch=8)
    assert isinstance(p, BatchCollection) and not p.wants_tokens
    p = make_collection_policy("streamed", group_size=4, min_microbatch=8,
                               preprocess_fraction=0.5)
    assert isinstance(p, StreamedCollection) and p.wants_tokens
    assert p.preprocess_fraction == 0.5
    with pytest.raises(ValueError, match="unknown collection policy"):
        make_collection_policy("nope", group_size=4, min_microbatch=8)
    with pytest.raises(ValueError):
        HybridRunner(RunnerConfig(collection="nope"), PERF)
    # the pre-CollectionPolicy name still resolves to the batch collector
    assert MicrobatchCollector is BatchCollection


def test_batch_policy_charges_full_and_ignores_tokens():
    p = BatchCollection(group_size=2, min_microbatch=2)
    r = _row(0, 0, 5)
    p.on_token(r)                                # no-op, no partial state
    p.note_rollout_done()
    assert p.charge([r], 3.0, 10.0) == (3.0, 0.0)


def test_streamed_partial_assembly_and_boundary_assert():
    p = StreamedCollection(group_size=2, min_microbatch=2)
    a, b = _row(0, 0, 0), _row(1, 0, 0)
    for _ in range(3):
        a.n_generated += 1
        p.on_token(a)
    b.n_generated += 1
    p.on_token(b)
    assert p._partial == {0: 3, 1: 1}
    assert p.n_stream_tokens == 4
    # a checkpoint with rows in flight is a bug, not a state to serialize
    with pytest.raises(AssertionError, match="partial rows in flight"):
        p.state_dict()
    a.completed_at, b.completed_at = 1.0, 2.0
    p.add(a)
    p.add(b)
    assert not p._partial
    assert p.n_rows_preprocessed == 2
    assert p.pop_microbatch() == [a, b]
    state = p.state_dict()
    assert state["n_stream_tokens"] == 4
    q = StreamedCollection(group_size=2, min_microbatch=2)
    q.load_state_dict(state)
    assert q.n_stream_tokens == 4 and q.n_rows_preprocessed == 2


def test_streamed_counts_version_straddlers():
    p = StreamedCollection(group_size=1, min_microbatch=1)
    clean, straddler = _row(0, 0, 4, 1.0), _row(1, 1, 4, 2.0)
    clean.version_spans = [[3, 4]]
    straddler.version_spans = [[3, 2], [4, 2]]   # mid-stream swap_weights
    p.add(clean)
    p.add(straddler)
    assert p.n_straddlers == 1


def test_streamed_tail_charge_math():
    p = StreamedCollection(group_size=2, min_microbatch=2,
                           preprocess_fraction=0.4)
    rows = [_row(0, 0, 10, completed_at=5.0),    # total_len 20
            _row(1, 0, 30, completed_at=9.0)]    # total_len 40
    # pre-tail pops are never credited (partition safety)
    assert p.charge(rows, 6.0, 10.0) == (6.0, 0.0)
    p.note_rollout_done()
    dt, credit = p.charge(rows, 6.0, 10.0)
    # shares: 0.4*6*(20/60) = 0.8, 0.4*6*(40/60) = 1.6
    # done-for: 5.0 s and 1.0 s -> credit = min(.8,5) + min(1.6,1) = 1.8
    assert credit == pytest.approx(1.8)
    assert dt == pytest.approx(4.2)
    assert p.overlap_s == pytest.approx(1.8)
    # a row that completed at the pop instant contributes nothing
    _, c2 = p.charge([_row(2, 1, 10, completed_at=10.0)], 6.0, 10.0)
    assert c2 == 0.0
    # credit never exceeds the microbatch's full cost
    dt3, c3 = p.charge(rows, 1.0, 1e9)
    assert c3 <= 1.0 and dt3 >= 0.0
    p.reset()
    assert not p._tail and not p._partial


# --------------------------------------------------------------------------- #
# sim: 5-seed chaos sweep — streamed and batch collect the same run
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_streamed_vs_batch_response_set_sim_chaos(seed):
    rb = _run(_mkcfg(seed, "batch"))
    rs = _run(_mkcfg(seed, "streamed"))
    assert rs.journal.response_set() == rb.journal.response_set()
    check_invariants(rs.manager, [], journal=rs.journal)
    # the stream actually ran: every token and every row went through it
    n_rows = len(rs.journal.response_set())
    assert rs.collector.n_rows_preprocessed == n_rows > 0
    assert rs.collector.n_stream_tokens > 0
    # and the tail flushes banked real overlap on the event clock
    assert rs.metrics[-1]["rollout.overlap_s"] > 0.0
    assert rs.collector.overlap_s == pytest.approx(
        rs.metrics[-1]["rollout.overlap_s"])
    # batch runs carry no streaming state at all
    assert "rollout.overlap_s" not in rb.metrics[-1]


def test_streamed_accounting_and_flush_spans():
    """The stall-accounting identity is untouched by streaming (overlap is
    a trainer-side counter, not a 7th instance-lane bucket), and the tail
    flushes appear as collect.flush spans carrying their credit."""
    from repro import obs
    r = _run(_mkcfg(3, "streamed", trace=True))
    report = obs.check_accounting(r.manager, tracer=r.tracer, now=r.loop.now)
    assert report["n_instances"] > 0
    flushes = [s for s in r.tracer.spans() if s.name == "collect.flush"]
    assert flushes
    assert sum(s.attrs["credit_s"] for s in flushes) == pytest.approx(
        r.collector.overlap_s)
    for s in flushes:
        assert s.t1 >= s.t0 and s.attrs["n_samples"] > 0
    summ = obs.summarize(r.metrics)
    assert 0.0 < summ["trainer_overlap_fraction"] < 1.0
    assert summ["trainer_overlap_s"] == pytest.approx(r.collector.overlap_s)


def test_streamed_first_step_strictly_faster_sim():
    """Tail-flush crediting shortens a step, never lengthens it.  Exact
    on the FIRST step, where both policies see an identical rollout
    timeline; from step 2 on, the seeding controller legitimately reacts
    to the earlier step end (remotes waited less), so later steps only
    keep the response-set contract (see the chaos sweep above)."""
    for seed in (0, 1):
        runs = {}
        for collection in ("batch", "streamed"):
            cfg = RunnerConfig(mode="rlboost", n_prompts=8, group_size=4,
                               mean_response=800, max_response=2048,
                               m_b=8, seed=seed, collection=collection)
            r = HybridRunner(cfg, PERF)
            r.load_trace([TraceEvent(0.0, +4)])
            r.run(n_steps=3)
            runs[collection] = r
        b0 = runs["batch"].metrics[0]
        s0 = runs["streamed"].metrics[0]
        credit0 = s0["rollout.overlap_s"]
        assert credit0 > 0.0
        assert s0["step.time_s"] == pytest.approx(
            b0["step.time_s"] - credit0)
        assert (runs["streamed"].journal.response_set()
                == runs["batch"].journal.response_set())


# --------------------------------------------------------------------------- #
# real backend: bit-identical params + staleness masking mid-swap
# --------------------------------------------------------------------------- #
def test_real_streamed_vs_batch_final_params_bit_identical():
    """Real compute, single tail flush per step (m_b = n_prompts * G): the
    grad-accumulation partition is identical by construction, so batch and
    streamed collection produce byte-equal params and optimizer state —
    while streamed banks nonzero overlap and finishes no later."""
    from repro.rl.harness import RealRLHarness, tiny_math_config

    def mkrc(collection):
        return RunnerConfig(mode="rlboost", n_prompts=2, group_size=2,
                            m_b=4, seed=0, t_seed_init=5.0,
                            collection=collection)

    cfg = tiny_math_config()
    trace = [TraceEvent(0.0, +2)]
    runs = {}
    for collection in ("batch", "streamed"):
        h = RealRLHarness(cfg, mkrc(collection), max_new=6)
        h.runner.load_trace(trace)
        metrics, rewards = h.run(3)
        runs[collection] = (h, metrics, rewards)
    hb, mb_, rwb = runs["batch"]
    hs, ms_, rws = runs["streamed"]
    # same rollouts consumed in the same partition...
    assert hs.runner.journal.response_set() == hb.runner.journal.response_set()
    assert [s["n"] for s in hs.staleness] == [s["n"] for s in hb.staleness]
    assert rws == rwb
    # ...to byte-equal trainer state
    for a, b in zip(jax.tree.leaves(hb.params), jax.tree.leaves(hs.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(hb.opt), jax.tree.leaves(hs.opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the overlap is real and shows up as wall-clock-of-the-event-clock:
    # from the same start, the first step ends earlier by exactly its
    # tail credit (later steps also see the seeding controller react to
    # the shorter remote wait, so their ends are not compared)
    assert ms_[-1]["rollout.overlap_s"] > 0.0
    assert ms_[0]["step.t_start"] == mb_[0]["step.t_start"]
    assert ms_[0]["step.t_end"] == pytest.approx(
        mb_[0]["step.t_end"] - ms_[0]["train.t_overlap_s"], abs=1e-9)
    # rewards were scored at row completion, and none were left behind
    assert hs.runner.collector.n_rows_preprocessed == 4 * 3
    assert not hs._reward_cache


def test_real_staleness_masking_after_midstream_swap():
    """A response straddling a mid-stream swap_weights is counted by the
    streamed collector as it arrives AND masked out of the loss by the
    harness's staleness gate — the same per-token version stamps feed
    both."""
    from repro.data import tokenizer as tok
    from repro.models import init_params
    from repro.rl.harness import RealRLHarness, tiny_math_config
    from repro.rl.sampler import request_key
    from repro.serving.engine import InferenceEngine

    cfg = tiny_math_config()
    params1 = init_params(cfg, jax.random.PRNGKey(0))
    params2 = jax.tree.map(lambda x: x * 1.01, params1)
    eng = InferenceEngine(cfg, params1, max_batch=4, slab_len=64,
                          temperature=1.0, weight_version=1)
    prompt = tok.encode("12+34=")
    reqs = {rid: Request(id=rid, group=0, prompt_len=len(prompt),
                         max_total=len(prompt) + 8, prompt_ids=prompt,
                         seed=0)
            for rid in (0, 1)}
    for rid, r in reqs.items():
        eng.add_request(rid, prompt, request_key(0, rid), r.max_total,
                        r.prompt_len)

    policy = StreamedCollection(group_size=2, min_microbatch=2)
    done = set()
    for step in range(40):
        if step == 3:        # v2 lands mid-generation: swap, don't drop
            eng.swap_weights(params2, 2)
        for ev in eng.step():
            r = reqs[ev.req_id]
            r.tokens.append(ev.token)
            r.logprobs.append(ev.logprob)
            r.stamp_version(ev.weight_version)
            r.n_generated += 1
            policy.on_token(r)
            if ev.finished:
                r.completed_at = float(step)
                policy.add(r)
                done.add(ev.req_id)
        if done == set(reqs):
            break
    assert done == {0, 1}
    assert policy.n_straddlers == 2              # both straddled the swap
    mb = policy.pop_microbatch()
    assert mb is not None and len(mb) == 2

    # the harness's loss-side gate masks exactly these rows
    h = RealRLHarness(cfg, RunnerConfig(mode="rlboost", n_prompts=2,
                                        group_size=2, m_b=4, seed=0),
                      max_new=6, staleness_limit=0)
    h.runner.store.version = 2                   # current published version
    batch = h._batch_from_requests(mb)
    assert h.n_stale_filtered == 2
    assert h.staleness[-1]["max"] == 1           # straddlers are 1 stale
    np.testing.assert_array_equal(np.asarray(batch["response_mask"]), 0.0)
    np.testing.assert_array_equal(np.asarray(batch["advantages"]), 0.0)
