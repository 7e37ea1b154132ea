"""Zero-recompute migration: KV pages ship over the chunk plane.

A request migrated mid-decode exports its pages as a content-addressed
chunk manifest (codec ``none`` bit-exact / ``int8`` per-page quant), and
the destination imports them and resumes at pos = len(prompt)+len(partial)
with ZERO prefill (counter-asserted).  GRPO siblings migrating together
ship their shared prompt pages once and re-adopt them by refcount; ring
/ SSM per-slot state rides as extra manifest leaves; repeated
export->import->free cycles leak no pages.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.events import EventLoop
from repro.core.rollout_manager import RolloutManager
from repro.core.perfmodel import ModelPerf, SPOT_INSTANCE, InstanceKind
from repro.core.requests import Request
from repro.core.weight_transfer import TransferAgent, WeightStore
from repro.data import tokenizer as tok
from repro.kernels import ref
from repro.models import init_params
from repro.rl.sampler import request_key
from repro.serving.engine import InferenceEngine
from repro.transfer import codec as codec_mod
from repro.transfer.chunkstore import (ChunkStore, LeafSpec,
                                       assemble_kv_state, build_kv_manifest)


def _mk(arch="qwen2-7b", temperature=1.0, seed=0, **eng_kw):
    cfg = get_config(arch).reduced(n_heads=2, n_kv_heads=1, d_model=32,
                                   head_dim=16, d_ff=64,
                                   vocab_size=tok.VOCAB_SIZE)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    kw = dict(max_batch=4, slab_len=64, temperature=temperature, page_size=8)
    kw.update(eng_kw)
    return cfg, params, (lambda: InferenceEngine(cfg, params, **kw))


def _drive(eng, rid, prompt, key, max_total, n_steps=None, add=True):
    if add:
        eng.add_request(rid, prompt, key, max_total, len(prompt))
    out, done = [], False
    while not done and (n_steps is None or len(out) < n_steps):
        evs = eng.step()
        mine = [e for e in evs if e.req_id == rid]
        if not mine:
            if rid not in eng.active_request_ids():
                break
            continue
        for e in mine:
            out.append((e.token, e.logprob))
            done = e.finished
    return out


def _migrate_via_manifest(src, dst, req_ids, codec="none",
                          chunk_bytes=1 << 12):
    """Export -> chunk manifest -> (local) blob fetch -> import."""
    state = src.export_request_state(req_ids)
    m, blobs, meta = build_kv_manifest(1, state, codec=codec,
                                       chunk_bytes=chunk_bytes)
    for rid in req_ids:
        src.drop_request(rid)
    dst.import_request_state(assemble_kv_state(m, blobs, meta))
    return state, m


# --------------------------------------------------------------------------- #
# bit-exactness (codec none)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_kv_migration_bit_exact_zero_prefill(temperature):
    cfg, params, mk = _mk(temperature=temperature)
    prompt = tok.encode("12+34=")
    key = request_key(7, 42)
    mt = len(prompt) + 24

    engA = mk()
    full = _drive(engA, 42, prompt, key, mt)

    engB = mk()
    part = _drive(engB, 42, prompt, key, mt, n_steps=6)
    _migrate_via_manifest(engB, engC := mk(), [42])
    rest = _drive(engC, 42, prompt, key, mt, add=False)

    assert [t for t, _ in part] + [t for t, _ in rest] == \
        [t for t, _ in full]
    np.testing.assert_allclose(
        [lp for _, lp in part] + [lp for _, lp in rest],
        [lp for _, lp in full], atol=1e-5)
    # zero-recompute: the destination never prefilled ANYTHING
    assert engC.n_prefills == 0 and engC.n_prefill_tokens == 0
    assert engC.n_kv_import_tokens == len(prompt) + len(part) - 1


@pytest.mark.parametrize("page_size", [4, 16])
def test_kv_migration_small_pages_unaligned_cut(page_size):
    cfg, params, mk = _mk(page_size=page_size, slab_len=32)
    prompt = tok.encode("25*4=")
    key = request_key(5, 9)
    mt = len(prompt) + 20

    engA = mk()
    full = _drive(engA, 9, prompt, key, mt)
    engB = mk()
    part = _drive(engB, 9, prompt, key, mt, n_steps=page_size + 1)
    _migrate_via_manifest(engB, engC := mk(), [9])
    rest = _drive(engC, 9, prompt, key, mt, add=False)
    assert [t for t, _ in part] + [t for t, _ in rest] == \
        [t for t, _ in full]
    assert engC.n_prefill_tokens == 0


def test_kv_migration_ring_and_per_slot_state():
    """Local-attention ring buffers (per-slot, non-paged) ride along in the
    same manifest and the continuation stays bit-exact."""
    cfg, params, mk = _mk(arch="gemma3-4b", max_batch=2, slab_len=32)
    assert not all(m == "global" for m in cfg.layer_mixers())
    prompt = tok.encode("7*6=")
    key = request_key(2, 5)
    mt = len(prompt) + 14
    engA = mk()
    full = _drive(engA, 5, prompt, key, mt)
    engB = mk()
    part = _drive(engB, 5, prompt, key, mt, n_steps=5)
    state, m = _migrate_via_manifest(engB, engC := mk(), [5])
    assert state["slot_state"], "ring K/V rows must be exported"
    assert any(spec.key.startswith("kv:slot:") for spec in m.leaves)
    rest = _drive(engC, 5, prompt, key, mt, add=False)
    assert [t for t, _ in part] + [t for t, _ in rest] == \
        [t for t, _ in full]
    assert engC.n_prefill_tokens == 0


# --------------------------------------------------------------------------- #
# GRPO group migration: shared prompt pages ship once, refcount adoption
# --------------------------------------------------------------------------- #
def _drive_group(eng, rids, n_steps=None):
    out = {r: [] for r in rids}
    done = set()
    steps = 0
    while len(done) < len(rids) and (n_steps is None or steps < n_steps):
        evs = eng.step()
        steps += 1
        for e in evs:
            if e.req_id in out and e.req_id not in done:
                out[e.req_id].append((e.token, e.logprob))
                if e.finished:
                    done.add(e.req_id)
    return out, done


def test_group_migration_ships_shared_prompt_pages_once():
    cfg, params, mk = _mk(temperature=1.0, page_size=4)
    prompt = tok.encode("123+456=")
    members = [(i, request_key(3, i), len(prompt) + 12) for i in range(3)]

    engA = mk()
    engA.add_group(members, prompt, len(prompt))
    ref_out, _ = _drive_group(engA, [0, 1, 2])

    engB = mk()
    engB.add_group(members, prompt, len(prompt))
    part, done = _drive_group(engB, [0, 1, 2], n_steps=4)
    assert not done, "siblings must still be mid-decode at the cut"

    state = engB.export_request_state([0, 1, 2])
    # shared prompt pages appear ONCE in the unique-page payload
    n_table_entries = sum(len(r["page_idx"]) for r in state["requests"])
    assert state["n_pages"] < n_table_entries
    m, blobs, meta = build_kv_manifest(2, state, codec="none",
                                       chunk_bytes=1 << 12)
    for rid in [0, 1, 2]:
        engB.drop_request(rid)

    engC = mk()
    engC.import_request_state(assemble_kv_state(m, blobs, meta))
    # refcount adoption: a fully-shared prompt page is held by all 3 tables
    shared = [p for p in {engC.slots[s].table[0]
                          for s in range(3) if engC.slots[s] is not None}]
    assert any(engC.alloc.ref[p] == 3 for p in shared)
    rest, _ = _drive_group(engC, [0, 1, 2])
    for rid in [0, 1, 2]:
        assert ([t for t, _ in part[rid]] + [t for t, _ in rest[rid]]
                == [t for t, _ in ref_out[rid]]), rid
    assert engC.n_prefill_tokens == 0


def test_mid_group_partial_migration():
    """Only a SUBSET of a group migrates: the destination allocates only
    the pages that subset references; the stay-behind sibling continues on
    the source — both remain bit-exact."""
    cfg, params, mk = _mk(temperature=1.0, page_size=4)
    prompt = tok.encode("9*9=")
    members = [(i, request_key(4, i), len(prompt) + 10) for i in range(3)]

    engA = mk()
    engA.add_group(members, prompt, len(prompt))
    ref_out, _ = _drive_group(engA, [0, 1, 2])

    engB = mk()
    engB.add_group(members, prompt, len(prompt))
    part, _ = _drive_group(engB, [0, 1, 2], n_steps=3)

    state = engB.export_request_state([0, 1, 2])
    m, blobs, meta = build_kv_manifest(3, state, codec="none",
                                       chunk_bytes=1 << 12)
    engB.drop_request(0)
    engB.drop_request(1)
    engC = mk()
    free0 = engC.alloc.n_free
    engC.import_request_state(assemble_kv_state(m, blobs, meta),
                              only=[0, 1])
    assert 2 not in engC.active_request_ids()
    # pages referenced ONLY by the stay-behind sibling were not allocated
    used = {i for r in state["requests"] if r["req_id"] in (0, 1)
            for i in r["page_idx"]}
    assert free0 - engC.alloc.n_free == len(used)

    restC, _ = _drive_group(engC, [0, 1])
    restB, _ = _drive_group(engB, [2])
    for rid, rest in [(0, restC[0]), (1, restC[1]), (2, restB[2])]:
        assert ([t for t, _ in part[rid]] + [t for t, _ in rest]
                == [t for t, _ in ref_out[rid]]), rid


# --------------------------------------------------------------------------- #
# int8 per-page codec: error bound vs the ref oracle
# --------------------------------------------------------------------------- #
def test_int8_kv_page_error_bound_vs_ref_oracle():
    rng = np.random.RandomState(0)
    page = rng.randn(2, 8, 16).astype(np.float32) * 3.0   # [K, ps, dh]
    payload = codec_mod.encode_leaf(page, "int8")
    spec = LeafSpec("kv:page:0:x", page.shape, "float32", "int8", 0,
                    len(payload))
    out = codec_mod.decode_leaf(payload, spec)
    # per-channel scale bound: |err| <= scale/2 per element
    flat = page.reshape(-1, page.shape[-1])
    scale = np.abs(flat).max(axis=0) / 127.0 + 1e-12
    err = np.abs(out.reshape(-1, page.shape[-1]) - flat)
    assert (err <= scale[None, :] / 2 + 1e-7).all()
    # the numpy decode path must agree with the kernel ref oracle
    n = page.size
    q = np.frombuffer(payload[:n], np.int8).reshape(-1, page.shape[-1])
    s = np.frombuffer(payload[n:], np.float32)
    oracle = np.asarray(ref.dequant_ref(q, s, None))
    np.testing.assert_allclose(out.reshape(oracle.shape), oracle, atol=0)


def test_int8_kv_migration_runs_and_bounds_state_error():
    """An int8 KV migration is LOSSY by design (cheap links); the imported
    pages must still be within the per-page quant bound of the source."""
    cfg, params, mk = _mk(temperature=0.0)
    prompt = tok.encode("12+34=")
    key = request_key(7, 8)
    mt = len(prompt) + 16
    engB = mk()
    _drive(engB, 8, prompt, key, mt, n_steps=5)
    state = engB.export_request_state([8])
    m, blobs, meta = build_kv_manifest(4, state, codec="int8",
                                       chunk_bytes=1 << 12)
    assert m.total_bytes < sum(np.asarray(v).nbytes
                               for v in state["pages"].values())
    s2 = assemble_kv_state(m, blobs, meta)
    for k, src in state["pages"].items():
        src = np.asarray(src, np.float32)
        got = np.asarray(s2["pages"][k], np.float32)
        flat = src.reshape(-1, src.shape[-1])
        scale = np.abs(flat).max(axis=0) / 127.0 + 1e-12
        assert (np.abs(got - src).reshape(-1, src.shape[-1])
                <= scale[None, :] / 2 + 1e-7).all(), k
    engC = mk()
    engC.import_request_state(s2)
    rest = _drive(engC, 8, prompt, key, mt, add=False)
    assert rest and engC.n_prefill_tokens == 0


# --------------------------------------------------------------------------- #
# allocator hygiene across export -> import -> free cycles
# --------------------------------------------------------------------------- #
def test_export_import_free_cycles_leak_no_pages():
    cfg, params, mk = _mk(temperature=1.0, page_size=4)
    prompt = tok.encode("11+22=")
    eng_src, eng_dst = mk(), mk()
    free_src0, free_dst0 = eng_src.alloc.n_free, eng_dst.alloc.n_free
    for cycle in range(3):
        members = [(100 * cycle + i, request_key(cycle, i),
                    len(prompt) + 8) for i in range(2)]
        eng_src.add_group(members, prompt, len(prompt))
        rids = [m[0] for m in members]
        _drive_group(eng_src, rids, n_steps=3)
        live = [r for r in rids if r in eng_src.active_request_ids()]
        if live:
            state = eng_src.export_request_state(live)
            m, blobs, meta = build_kv_manifest(10 + cycle, state,
                                               codec="none")
            for rid in live:
                eng_src.drop_request(rid)
            eng_dst.import_request_state(assemble_kv_state(m, blobs, meta))
            _drive_group(eng_dst, live)          # run to completion (frees)
    assert eng_src.alloc.n_free == free_src0
    assert eng_dst.alloc.n_free == free_dst0
    assert (eng_src.alloc.ref[1:] == 0).all()
    assert (eng_dst.alloc.ref[1:] == 0).all()


# --------------------------------------------------------------------------- #
# manager-level: migration mid-decode through the full chunk-pull path
# --------------------------------------------------------------------------- #
def _manager_world(mk_engine, perf, migration="auto", kv_codec="none"):
    loop = EventLoop()
    store = WeightStore([TransferAgent(0, 400.0)],
                        chunkstore=ChunkStore(chunk_bytes=1 << 12))
    mgr = RolloutManager(loop, perf, store, engine_factory=mk_engine,
                         migration=migration, kv_codec=kv_codec,
                         max_exec_per_instance=4)
    return loop, store, mgr


def test_manager_level_kv_migration_bit_exact_and_spans():
    """A request migrated mid-decode between two REAL engines through the
    export -> manifest -> ChunkPull -> import path emits bit-identical
    tokens / logprobs / version spans vs an unmigrated run, and no engine
    re-prefills migrated context (globally: each prompt prefills once)."""
    cfg, params, mk = _mk(temperature=1.0)
    perf = ModelPerf(n_params=1e9, n_active=1e9)
    prompts = [tok.encode(p) for p in ["12+34=", "9*8=", "7-5="]]

    def run(migrate: bool):
        loop, store, mgr = _manager_world(mk, perf, migration="kv")
        store.publish(1, params)
        mgr.required_version = 1
        engines = []
        orig_factory = mgr.engine_factory

        def factory():
            e = orig_factory()
            engines.append(e)
            return e
        mgr.engine_factory = factory
        kind = InstanceKind(SPOT_INSTANCE.name, SPOT_INSTANCE.chips, 50.0)
        i0 = mgr.allocate(kind=kind)
        i1 = mgr.allocate(kind=kind)
        reqs = [Request(id=i, group=i, prompt_len=len(p),
                        max_total=len(p) + 12, prompt_ids=p, seed=3)
                for i, p in enumerate(prompts)]
        done = []
        mgr.on_complete_cb = done.append
        loop.run(until=50.0)                      # weight pulls land
        mgr.submit(reqs)
        moved = []

        def try_migrate():
            if moved:
                return
            for src, dst in [(i0, i1), (i1, i0)]:
                for rid, r in list(src.executing.items()):
                    if r.n_generated >= 3:
                        src.export_kv_requests([r])
                        taken = src.take_back(rid)
                        assert taken is r and r.kv is not None
                        dst.assign(r)
                        moved.append(rid)
                        return

        if migrate:
            mgr.on_token_cb = lambda r: loop.schedule(0.0, try_migrate)
        loop.run(until=500.0)             # the LB tick reschedules forever
        assert len(done) == len(reqs)
        if migrate:
            assert moved and mgr.n_kv_migrations >= 1
        total_prefill = sum(e.n_prefill_tokens for e in engines)
        # zero recompute: globally each prompt prefilled exactly once even
        # in the migrated run
        assert total_prefill == sum(len(p) for p in prompts)
        return {r.id: (list(r.tokens), list(r.logprobs),
                       [list(s) for s in r.version_spans]) for r in reqs}

    base = run(migrate=False)
    mig = run(migrate=True)
    for rid in base:
        assert mig[rid][0] == base[rid][0], rid           # tokens
        np.testing.assert_allclose(mig[rid][1], base[rid][1], atol=1e-5)
        assert mig[rid][2] == base[rid][2], rid           # version spans


def test_manager_auto_cost_model_prefers_prefill_for_short_context():
    """With a huge fixed migration overhead the cost model must fall back
    to the re-prefill path (kv cleared, request still completes)."""
    cfg, params, mk = _mk(temperature=1.0)
    perf = ModelPerf(n_params=1e9, n_active=1e9,
                     migration_overhead_s=1e9)
    loop, store, mgr = _manager_world(mk, perf)
    store.publish(1, params)
    mgr.required_version = 1
    i0 = mgr.allocate()
    i1 = mgr.allocate()
    p = tok.encode("1+1=")
    r = Request(id=0, group=0, prompt_len=len(p), max_total=len(p) + 10,
                prompt_ids=p, seed=1)
    done = []
    mgr.on_complete_cb = done.append
    loop.run(until=50.0)
    mgr.submit([r])
    migrated = []

    def try_migrate():
        if migrated:
            return
        for src, dst in [(i0, i1), (i1, i0)]:
            if r.id in src.executing and r.n_generated >= 2:
                src.export_kv_requests([r])
                dst.assign(src.take_back(r.id))
                migrated.append(True)
                return
    mgr.on_token_cb = lambda _: loop.schedule(0.0, try_migrate)
    loop.run(until=500.0)
    assert done and migrated
    assert mgr.n_kv_migrations == 0
    assert mgr.n_prefill_migrations == 1
    assert r.kv is None and r.n_generated >= 10 - 1
