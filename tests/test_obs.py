"""Flight-recorder tests (PR 7): tracer/metrics/accounting units, the
5-seed chaos sweep property (instance time decomposes exactly into the
stall-accounting buckets; every span is well-formed), registry dotted
names matching the legacy accessors, and the Perfetto export shape."""

import json

import pytest

from repro import obs
from repro.configs import get_config
from repro.core.faults import FAULT_COUNTERS, FaultPlan, FaultStats
from repro.core.hybrid_runtime import HybridRunner, RunnerConfig
from repro.core.perfmodel import model_perf_from_cfg
from repro.core.spot_trace import TraceEvent
from repro.obs.accounting import (AccountingError, BUCKETS, LaneAccount,
                                  aggregate, check_accounting)
from repro.obs.metrics import MetricsRegistry, RegistryCounter, summarize
from repro.obs.tracer import NULL_TRACER, Tracer


# --------------------------------------------------------------------------- #
# tracer unit
# --------------------------------------------------------------------------- #
def test_tracer_records_parented_spans_and_instants():
    t = [0.0]
    tr = Tracer(lambda: t[0])
    root = tr.begin("rl.step", "trainer", step=0)
    t[0] = 2.0
    child = tr.begin("pull.weights", "inst:1", parent=root, version=3)
    t[0] = 5.0
    tr.end(child, outcome="ok")
    tr.event("swap.weights", "inst:1", parent=root)
    tr.end(root)
    spans = tr.spans()
    assert [s.name for s in spans] == ["rl.step", "pull.weights",
                                      "swap.weights"]
    assert spans[1].parent_id == root.span_id
    assert spans[1].t0 == 2.0 and spans[1].t1 == 5.0
    assert spans[1].attrs == dict(version=3, outcome="ok")
    assert spans[2].duration == 0.0
    assert set(tr.lanes()) == {"trainer", "inst:1"}


def test_tracer_retroactive_and_idempotent_end():
    tr = Tracer(lambda: 100.0)
    s = tr.begin("decode.horizon", "inst:0", t0=7.0)
    tr.end(s, t1=9.0)
    tr.end(s, t1=50.0)                   # double-close: first one wins
    assert (s.t0, s.t1) == (7.0, 9.0)


def test_tracer_ring_is_bounded():
    tr = Tracer(lambda: 0.0, capacity=8)
    for i in range(100):
        tr.event("e", "lane", i=i)
    spans = tr.spans()
    assert len(spans) == 8
    assert spans[-1].attrs["i"] == 99


def test_tracer_jsonl_sink(tmp_path):
    p = tmp_path / "spans.jsonl"
    tr = Tracer(lambda: 1.5, jsonl_path=str(p))
    tr.end(tr.begin("a", "l"))
    tr.event("b", "l")
    tr.close()
    rows = [json.loads(x) for x in p.read_text().splitlines()]
    assert [r["name"] for r in rows] == ["a", "b"]
    assert all(r["t1"] is not None for r in rows)


def test_null_tracer_is_inert():
    s = NULL_TRACER.begin("x", "lane")
    assert NULL_TRACER.end(s) is s
    with NULL_TRACER.span("y", "lane") as sp:
        assert sp is s
    assert NULL_TRACER.spans() == []
    assert not NULL_TRACER.enabled


def test_tracer_annotate_writes_span_names_to_the_profiler(tmp_path):
    """``annotate=True``: each span opens a profiler annotation of its plain
    name (attrs stay in the ring); ``annotate=False`` spans stay out."""
    import glob
    import time

    import jax

    tr = Tracer(time.perf_counter, annotate=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("obs.step", "engine", rows=3) as step:
            tr.end(tr.begin("obs.step.wait", "engine", parent=step))
            tr.end(tr.begin("obs.queued", "engine", annotate=False))
    finally:
        jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0])
    host = {e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}
    assert {"obs.step", "obs.step.wait"} <= host
    assert "obs.queued" not in host
    spans = tr.spans()
    assert [s.name for s in spans] == ["obs.step", "obs.step.wait",
                                      "obs.queued"]
    assert spans[0].attrs == {"rows": 3} and spans[1].parent_id == step.span_id
    assert all(s.closed for s in spans)


# --------------------------------------------------------------------------- #
# metrics registry unit
# --------------------------------------------------------------------------- #
def test_registry_counters_gauges_histograms_views():
    reg = MetricsRegistry()
    reg.inc("a.n", 2)
    reg.inc("a.n")
    reg.gauge("b.x", 7.5)
    reg.observe("c.dur", 1.0)
    reg.observe("c.dur", 3.0)
    reg.register_view("d", lambda: {"k": 42})
    snap = reg.snapshot()
    assert snap["a.n"] == 3
    assert snap["b.x"] == 7.5
    assert snap["c.dur.count"] == 2 and snap["c.dur.mean"] == 2.0
    assert snap["d.k"] == 42


def test_registry_counter_descriptor_keeps_plain_attr_semantics():
    class Owner:
        n_foo = RegistryCounter("plane.n_foo")

        def __init__(self):
            self.registry = MetricsRegistry()
            self.n_foo = 0

    o = Owner()
    o.n_foo += 1
    o.n_foo += 1
    assert o.n_foo == 2
    assert o.registry.counters["plane.n_foo"] == 2


def test_fault_stats_is_a_registry_view():
    reg = MetricsRegistry()
    fs = FaultStats(reg)
    fs.n_corrupt_chunks += 3
    assert reg.counters["faults.n_corrupt_chunks"] == 3
    assert fs.as_dict()["n_corrupt_chunks"] == 3
    assert set(fs.as_dict()) == set(FAULT_COUNTERS)
    lone = FaultStats()                 # standalone: private registry
    lone.n_pull_replans += 1
    assert lone.n_pull_replans == 1


# --------------------------------------------------------------------------- #
# lane accounting unit
# --------------------------------------------------------------------------- #
def test_lane_account_credits_outgoing_state():
    a = LaneAccount(10.0)
    a.transition("busy", 10.0, split=(1.0, 0.0))    # idle [10,10] = 0
    a.transition("pull_stall", 14.0)                # busy 4s, all decode
    a.transition("idle", 15.0)                      # pull_stall 1s
    a.close(18.0)                                   # idle 3s
    tot = a.totals(18.0)
    assert tot["busy_decode"] == pytest.approx(4.0)
    assert tot["busy_prefill"] == 0.0
    assert tot["pull_stall"] == pytest.approx(1.0)
    assert tot["idle"] == pytest.approx(3.0)
    assert sum(tot.values()) == pytest.approx(a.elapsed(18.0))


def test_lane_account_busy_split_pro_rata():
    a = LaneAccount(0.0)
    a.transition("busy", 0.0, split=(3.0, 1.0))     # decode:prefill = 3:1
    a.close(8.0)
    tot = a.totals(8.0)
    assert tot["busy_decode"] == pytest.approx(6.0)
    assert tot["busy_prefill"] == pytest.approx(2.0)


def test_aggregate_includes_open_tail():
    a = LaneAccount(0.0)
    a.transition("busy", 0.0, split=(1.0, 0.0))
    agg = aggregate([("i0", a)], 5.0)               # still open at now=5
    assert agg["elapsed_s"] == pytest.approx(5.0)
    assert agg["busy_decode_s"] == pytest.approx(5.0)
    assert set(agg) == {f"{b}_s" for b in BUCKETS} | {"elapsed_s"}


def test_check_accounting_rejects_leaky_buckets():
    class FakeManager:
        def __init__(self):
            a = LaneAccount(0.0)
            a.close(10.0)
            a.buckets["idle"] = 3.0                 # 3s vanished from idle
            self._a = a

        def accounts(self):
            return [("i0", self._a)]

    with pytest.raises(AccountingError, match="i0"):
        check_accounting(FakeManager(), now=10.0)


# --------------------------------------------------------------------------- #
# the chaos-sweep property (satellite: >= 5 seeds)
# --------------------------------------------------------------------------- #
def _chaos_runner(seed: int) -> HybridRunner:
    cfg_m = get_config("qwen3-8b")
    plan = FaultPlan(seed=seed, corrupt_p=0.02, prune_p=0.01, stall_p=0.02,
                     stall_s=2.0, hard_kill_fraction=0.5, grace_s=2.0)
    rc = RunnerConfig(mode="rlboost", n_prompts=8, group_size=4,
                      mean_response=800, max_response=2048, m_b=8,
                      seed=seed, t_seed_init=10.0, transfer_chunks=8,
                      length_sigma=0.4, fault_plan=plan, trace=True)
    r = HybridRunner(rc, model_perf_from_cfg(cfg_m), model_cfg=cfg_m)
    r.load_trace([TraceEvent(0.0, 6), TraceEvent(6.0, -3),
                  TraceEvent(11.0, 3), TraceEvent(16.0, -2),
                  TraceEvent(22.0, 2), TraceEvent(27.0, -3),
                  TraceEvent(31.0, 3)])
    return r


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_chaos_time_decomposition_and_span_wellformedness(seed):
    """Property: under seeded chaos, every rollout instance's clock
    decomposes EXACTLY into busy(prefill)+busy(decode)+pull_stall+
    migration_stall+grace+idle, and every recorded span is well-formed
    (closed, non-negative duration, parent opened before child)."""
    r = _chaos_runner(seed)
    metrics = r.run(n_steps=2)
    report = check_accounting(r.manager, tracer=r.tracer, now=r.loop.now)
    assert report["n_instances"] > 0
    assert report["n_spans"] > 0
    assert report["elapsed_s"] > 0
    # the aggregate the runner snapshotted at the last step matches a
    # recomputation from the same accounts
    last = metrics[-1]
    for b in BUCKETS:
        assert last[f"obs.{b}_s"] <= report[f"{b}_s"] + 1e-9
    # stalls + busy exist under churn: preemptions force pulls/migrations
    assert r.manager.n_preemptions > 0
    assert report["busy_decode_s"] > 0


def test_chaos_run_metrics_match_legacy_accessors():
    r = _chaos_runner(seed=2)
    metrics = r.run(n_steps=2)
    last = metrics[-1]
    mgr = r.manager
    assert last["migration.n_migrations"] == mgr.n_migrations
    assert last["migration.n_preemptions"] == mgr.n_preemptions
    assert last["migration.n_restarts"] == mgr.n_restarts
    assert last["transfer.pull.n_chunk_fetches"] == mgr.n_chunk_fetches
    assert last["transfer.pull.n_cache_hits"] == mgr.n_chunk_cache_hits
    for name in FAULT_COUNTERS:
        assert last[f"faults.{name}"] == getattr(mgr.fault_stats, name)
    # per-step gauges carry the stable dotted names
    for key in ("step.idx", "step.tokens", "step.throughput",
                "seed.t_seed", "rollout.n_remote", "train.t_train_s",
                "obs.elapsed_s"):
        assert key in last


def test_summarize_fractions_partition_unity():
    r = _chaos_runner(seed=3)
    metrics = r.run(n_steps=2)
    s = summarize(metrics)
    assert s["steps"] == 2
    assert s["tokens"] > 0
    assert s["throughput"] == pytest.approx(
        s["tokens"] / s["duration"], rel=1e-6)
    total = sum(s[f"{b}_fraction"] for b in BUCKETS)
    assert total == pytest.approx(1.0, abs=1e-6)
    assert summarize([]) == dict(steps=0, tokens=0, duration=0.0,
                                 throughput=0.0)


# --------------------------------------------------------------------------- #
# the grace bucket carries real modeled time (recovery-plane satellite)
# --------------------------------------------------------------------------- #
def test_grace_bucket_accounts_modeled_export_time():
    """A soft preemption with exportable executing KV spends the summed
    modeled export time in the ``grace`` state: the lane records a true
    ``preempt.grace`` span, the grace bucket equals the span's duration,
    and the six-bucket identity still partitions every lane's clock."""
    from repro.core.events import EventLoop
    from repro.core.perfmodel import ModelPerf
    from repro.core.requests import Request
    from repro.core.rollout_manager import RolloutManager
    from repro.core.weight_transfer import TransferAgent, WeightStore
    from repro.obs.tracer import Tracer

    cfg_m = get_config("qwen3-8b")               # real KV bytes to export
    loop = EventLoop()
    store = WeightStore([TransferAgent(0, 400.0)], weight_bytes=8e9,
                        sim_chunks=4)
    mgr = RolloutManager(loop, model_perf_from_cfg(cfg_m), store,
                         cfg=cfg_m, migration="kv",
                         tracer=Tracer(lambda: loop.now))
    i0 = mgr.allocate()
    reqs = [Request(id=i, group=i // 2, prompt_len=512, max_total=1024,
                    target_total=800, seed=0) for i in range(4)]
    mgr.submit(reqs)
    fired = []

    def strike(r):
        if not fired and r.n_generated >= 4:
            fired.append(True)
            loop.schedule(0.0, lambda: mgr.preempt(i0, grace_s=float("inf")))
    mgr.on_token_cb = strike
    loop.run(until=600.0)
    assert fired
    spans = [s for s in mgr.tracer.spans() if s.name == "preempt.grace"]
    assert len(spans) == 1 and spans[0].closed
    dur = spans[0].t1 - spans[0].t0
    assert dur > 0.0                             # a TRUE span, not instant
    report = check_accounting(mgr, tracer=mgr.tracer, now=loop.now)
    assert report["grace_s"] == pytest.approx(dur)
    # the dying lane billed its grace window to spot cost
    assert mgr.spot_seconds >= dur
    # the killed instance left the fleet only after the window elapsed
    assert i0.id not in mgr.instances
    mgr.allocate()
    loop.run(until=6000.0)
    assert all(r.done for r in reqs)


def test_hard_kill_grace_is_instant():
    """grace_s=0 (hard kill): nothing exportable, the lane dies at the
    notice instant and the grace bucket stays zero."""
    from repro.core.events import EventLoop
    from repro.core.requests import Request
    from repro.core.rollout_manager import RolloutManager
    from repro.core.weight_transfer import TransferAgent, WeightStore
    from repro.core.perfmodel import ModelPerf
    from repro.obs.tracer import Tracer

    loop = EventLoop()
    store = WeightStore([TransferAgent(0, 400.0)], weight_bytes=8e9,
                        sim_chunks=4)
    mgr = RolloutManager(loop, ModelPerf(n_params=1e9, n_active=1e9), store,
                         tracer=Tracer(lambda: loop.now))
    i0 = mgr.allocate()
    reqs = [Request(id=i, group=i, prompt_len=16, max_total=64,
                    target_total=48, seed=0) for i in range(3)]
    mgr.submit(reqs)
    fired = []

    def strike(r):
        if not fired and r.n_generated >= 3:
            fired.append(True)
            loop.schedule(0.0, lambda: mgr.preempt(i0, grace_s=0.0))
    mgr.on_token_cb = strike
    loop.run(until=300.0)
    assert fired
    assert i0.id not in mgr.instances            # died at the notice
    assert not any(s.name == "preempt.grace" and s.t1 > s.t0
                   for s in mgr.tracer.spans())
    report = check_accounting(mgr, tracer=mgr.tracer, now=loop.now)
    assert report["grace_s"] == 0.0


# --------------------------------------------------------------------------- #
# perfetto export
# --------------------------------------------------------------------------- #
def test_perfetto_export_one_lane_per_instance(tmp_path):
    r = _chaos_runner(seed=4)
    r.run(n_steps=2)
    path = tmp_path / "trace.json"
    out = obs.export_chrome_trace(r.tracer, path)
    assert json.loads(path.read_text()) == out
    events = out["traceEvents"]
    lane_names = {e["args"]["name"] for e in events
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    # one lane per instance that recorded anything, + trainer + NICs
    inst_lanes = {s.lane for s in r.tracer.spans()
                  if s.lane.startswith("inst:")}
    assert inst_lanes and inst_lanes <= lane_names
    assert "trainer" in lane_names
    assert any(name.startswith("nic:") for name in lane_names)
    names = {e["name"] for e in events if e["ph"] in ("X", "i")}
    for required in ("rl.step", "seed.window", "train.microbatch",
                     "prefill.chunk", "decode.horizon", "pull.weights",
                     "transfer.chunk", "preempt.grace", "instance.dead"):
        assert required in names, required
    # complete events carry microsecond ts/dur and non-negative durations
    xs = [e for e in events if e["ph"] == "X"]
    assert xs and all(e["dur"] >= 0 for e in xs)


# --------------------------------------------------------------------------- #
# real engine on the wall clock
# --------------------------------------------------------------------------- #
def test_engine_spans_cover_step_swap_and_kv_migration():
    """The real engine traces on a wall clock: step() brackets decode and
    prefill, swap_weights leaves an instant, and a KV export/import pair
    is spanned on both ends of the migration."""
    import jax

    from repro.data import tokenizer as tok
    from repro.models import init_params
    from repro.rl.sampler import request_key
    from repro.serving.engine import InferenceEngine

    cfg = get_config("qwen2-7b").reduced(n_heads=2, n_kv_heads=1, d_model=32,
                                         head_dim=16, d_ff=64,
                                         vocab_size=tok.VOCAB_SIZE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    clock = [0.0]

    def tick():
        clock[0] += 0.25             # deterministic monotone "wall" clock
        return clock[0]

    tr = Tracer(tick)
    kw = dict(max_batch=4, slab_len=64, temperature=1.0, page_size=8,
              use_pallas=False)
    prompt = tok.encode("12+34=")
    max_total = len(prompt) + 12

    def run3(engine, key):
        engine.add_request(0, prompt, key, max_total, len(prompt))
        for _ in range(3):
            engine.step()
        return 0 in engine.exportable_request_ids()

    # the migration needs a request still decoding after 3 steps: take the
    # first key whose sampled stream has not hit EOS by then
    key = next(k for k in (request_key(0, i) for i in range(32))
               if run3(InferenceEngine(cfg, params, **kw), k))
    src = InferenceEngine(cfg, params, tracer=tr, **kw)
    dst = InferenceEngine(cfg, params, tracer=tr, **kw)
    assert run3(src, key)
    src.swap_weights(params, version=7)
    state = src.export_request_state([0])
    src.drop_request(0)
    dst.import_request_state(state)
    dst.step()

    spans = tr.spans()
    names = [s.name for s in spans]
    assert names.count("engine.decode") >= 4       # 3 src steps + 1 dst
    assert names.count("engine.prefill") >= 4
    assert "engine.kv_export" in names and "engine.kv_import" in names
    swap = next(s for s in spans if s.name == "engine.swap_weights")
    assert swap.duration == 0.0 and swap.attrs["version"] == 7
    assert set(tr.lanes()) == {"engine"}
    for s in spans:
        assert s.t1 is not None and s.t1 >= s.t0   # well-formed, closed


_PHASE_PARENT = {"engine.decode.host": "engine.decode",
                 "engine.decode.wait": "engine.decode",
                 "engine.decode.unpack": "engine.decode",
                 "engine.prefill.host": "engine.prefill",
                 "engine.prefill.wait": "engine.prefill",
                 "engine.sample": "engine.prefill",
                 "engine.sample.wait": "engine.sample"}


@pytest.mark.parametrize("horizon", [1, 2])
def test_engine_phase_spans_step_counters_and_queue_wait(horizon):
    """Inside step(): every phase child hangs off its phase and lies inside
    it, the step counters ride on the phases, and each admitted row gets one
    ``engine.queued`` span that closes served or dropped.  The same drive
    under NULL_TRACER records nothing and emits the same tokens."""
    import jax

    from repro.data import tokenizer as tok
    from repro.models import init_params
    from repro.rl.sampler import request_key
    from repro.serving.engine import InferenceEngine

    cfg = get_config("qwen2-7b").reduced(n_heads=2, n_kv_heads=1, d_model=32,
                                         head_dim=16, d_ff=64,
                                         vocab_size=tok.VOCAB_SIZE)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = tok.encode("12+34=" * 3)
    L = len(prompt)                      # 19 tokens: chunks of 8, 8 and 3
    clock = [0.0]

    def tick():
        clock[0] += 0.25
        return clock[0]

    def drive(tracer):
        eng = InferenceEngine(cfg, params, max_batch=4, slab_len=64,
                              temperature=1.0, page_size=8, prefill_chunk=8,
                              horizon=horizon, use_pallas=False, tracer=tracer)
        eng.add_group([(0, request_key(0, 0), L + 6),
                       (1, request_key(0, 1), L + 6)], prompt, L)
        eng.add_request(2, prompt[:5], request_key(0, 2), 11, 5)
        eng.add_request(3, prompt, request_key(0, 3), L + 6, L)
        out = [(e.req_id, e.token) for e in eng.step()]
        eng.drop_request(3)              # still waiting: nothing prefilled
        for _ in range(9):
            out += [(e.req_id, e.token) for e in eng.step()]
        return out

    tr = Tracer(tick)
    events = drive(tr)
    spans = tr.spans()
    by_id = {s.span_id: s for s in spans}
    assert {s.name for s in spans} == (set(_PHASE_PARENT) | {
        "engine.decode", "engine.prefill", "engine.queued"})
    for s in spans:
        assert s.closed
        if s.name in _PHASE_PARENT:      # .wait spans among them, and only
            p = by_id[s.parent_id]
            assert p.name == _PHASE_PARENT[s.name]
            assert p.t0 <= s.t0 <= s.t1 <= p.t1
    dec = [s.attrs for s in spans if s.name == "engine.decode"]
    pre = [s.attrs for s in spans if s.name == "engine.prefill"]
    assert len(dec) == len(pre) == 10
    for a in dec:
        assert a["rows"] == len(a["ctx"]) and a["pages_used"] >= 0
        assert isinstance(a["new_program"], bool)
    # three members at L + 6 tokens, request 2 at 11: pages of 8
    assert dec[0]["rows"] == 0
    assert dec[0]["pages_committed"] == 3 * -(-(L + 6) // 8) + 2
    for a in pre:
        assert isinstance(a["new_program"], bool)
    assert pre[0]["rows"] == [(0, 8, False)]
    assert pre[2]["rows"] == [(16, L - 16, True), (0, 5, True)]
    assert dec[3]["rows"] == 3 and dec[3]["ctx"] == [L + 1, L + 1, 6]
    queued = [s for s in spans if s.name == "engine.queued"]
    assert [(s.attrs["req"], s.attrs["tokens"], s.attrs["outcome"])
            for s in queued] == [([0, 1], L, "served"), ([2], 5, "served"),
                                 ([3], L, "dropped")]
    prefill3 = [s for s in spans if s.name == "engine.prefill"][2]
    assert all(prefill3.t0 < s.t1 < prefill3.t1 for s in queued[:2])
    assert drive(NULL_TRACER) == events and NULL_TRACER.spans() == []
