"""Unified LM backbone for all assigned architectures.

Structure: embed -> [prefix layers] -> scan over layer *groups* -> [suffix
layers] -> final norm.  A group is one repetition of ``cfg.pattern`` (e.g.
gemma3's 5xlocal+1xglobal); group params are stacked on a leading n_groups
axis so the whole depth lowers as a single ``lax.scan`` (compile-time and
HLO-size control for the 512-device dry-run).

Three modes share the layer code:
  train   — full sequence, no cache, returns final hidden states
  prefill — full sequence, fills the provided fresh cache, returns hidden
  decode  — one token per slot against the cache (per-slot positions)
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import kv_cache as kvc
from repro.models.attention import (apply_rope, attention_decode,
                                    attention_fwd, rope_inv_freq)
from repro.models.layers import (apply_mlp, dense_init, dtype_of,
                                 embed_tokens, init_mlp, rms_norm, softcap)
from repro.models.moe import init_moe_params, moe_layer
from repro.models.ssm import (init_mamba_params, mamba_mixer_decode,
                              mamba_mixer_fwd)


@dataclass(frozen=True)
class ModelRuntime:
    """Execution-context knobs threaded through the model."""
    mesh: Any = None
    data_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    ep_size: int = 1
    use_pallas: bool = False
    q_block: int = 512
    ssd_chunk: int = 128
    remat: bool = True

    def _axis_size(self, axes) -> int:
        n = 1
        for a in (axes if isinstance(axes, tuple) else (axes,)):
            if a is not None:
                n *= self.mesh.shape[a]
        return n

    def shard_act(self, x, *tail):
        """Pin activation sharding: batch over data axes (+ optional tail
        axes per dim).  No-op off-mesh or when dims don't divide."""
        if self.mesh is None or not self.data_axes or x is None:
            return x
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        entries = [self.data_axes] + list(tail)
        entries += [None] * (x.ndim - len(entries))
        spec = []
        for dim, axes in enumerate(entries[:x.ndim]):
            if axes is not None and x.shape[dim] % self._axis_size(axes) == 0:
                spec.append(axes)
            else:
                spec.append(None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))


CPU_RT = ModelRuntime(remat=False, q_block=128, ssd_chunk=32)


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #
def _init_attn(key, cfg: ModelConfig, dtype):
    D, H, K, dh = cfg.d_model, cfg.n_heads_eff, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H, dh), D, dtype),
        "wk": dense_init(ks[1], (D, K, dh), D, dtype),
        "wv": dense_init(ks[2], (D, K, dh), D, dtype),
        "wo": dense_init(ks[3], (H, dh, D), H * dh, dtype),
    }
    if cfg.pad_heads:
        # heads at the tail of each GQA group are padding: zero their output
        # rows so they contribute nothing (model == unpadded n_heads model)
        Gp = H // K
        Gr = cfg.n_heads // K
        alive = (jnp.arange(H) % Gp) < Gr
        p["wo"] = p["wo"] * alive[:, None, None].astype(dtype)
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, dh), dtype)
        p["bk"] = jnp.zeros((K, dh), dtype)
        p["bv"] = jnp.zeros((K, dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    return p


def _init_layer(key, cfg: ModelConfig, mixer: str, mlp_kind: str, d_ff: int,
                dtype):
    D = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Dict = {"ln1": {"scale": jnp.zeros((D,), jnp.float32)}}
    if mixer in ("global", "local", "hybrid"):
        p["attn"] = _init_attn(ks[0], cfg, dtype)
    if mixer in ("mamba", "hybrid"):
        p["mamba"] = init_mamba_params(ks[1], cfg, dtype)
    if mixer == "hybrid":
        p["attn_norm"] = {"scale": jnp.zeros((D,), jnp.float32)}
        p["ssm_norm"] = {"scale": jnp.zeros((D,), jnp.float32)}
    if cfg.post_norms:
        p["post_ln1"] = {"scale": jnp.zeros((D,), jnp.float32)}
    if mlp_kind != "none":
        p["ln2"] = {"scale": jnp.zeros((D,), jnp.float32)}
        if mlp_kind == "moe":
            p["mlp"] = init_moe_params(ks[2], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[2], D, d_ff, dtype)
        if cfg.post_norms:
            p["post_ln2"] = {"scale": jnp.zeros((D,), jnp.float32)}
    return p


def init_params(cfg: ModelConfig, key) -> Dict:
    dtype = dtype_of(cfg)
    mixers = cfg.layer_mixers()
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params: Dict = {"final_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}}

    if cfg.input_mode == "tokens" or cfg.is_decoder:
        params["embed"] = dense_init(k_embed, (cfg.vocab_size, cfg.d_model),
                                     cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                       cfg.d_model, dtype)

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    li = 0
    params["prefix"] = {}
    for i in range(cfg.first_k_dense):
        params["prefix"][str(i)] = _init_layer(
            layer_keys[li], cfg, mixers[li], "dense", cfg.d_ff_dense_prefix,
            dtype)
        li += 1

    G = cfg.n_groups
    groups: Dict = {}
    per_slot = [[] for _ in cfg.pattern]
    for g in range(G):
        for j, mixer in enumerate(cfg.pattern):
            per_slot[j].append(_init_layer(
                layer_keys[li], cfg, mixer, cfg.mlp_kind, cfg.d_ff, dtype))
            li += 1
    for j in range(len(cfg.pattern)):
        groups[f"sub{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_slot[j])
    params["groups"] = groups

    params["suffix"] = {}
    for i, mixer in enumerate(cfg.suffix_pattern):
        params["suffix"][str(i)] = _init_layer(
            layer_keys[li], cfg, mixer, cfg.mlp_kind, cfg.d_ff, dtype)
        li += 1
    assert li == cfg.n_layers
    return params


# --------------------------------------------------------------------------- #
# layer application
# --------------------------------------------------------------------------- #
def _attn_apply(p, h, cfg: ModelConfig, rt: ModelRuntime, mixer: str,
                mode: str, cache, positions, lens=None, paged=None):
    B, S, D = h.shape
    H, K, dh = cfg.n_heads_eff, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhx->bshx", h, p["wq"])
    k = jnp.einsum("bsd,dkx->bskx", h, p["wk"])
    v = jnp.einsum("bsd,dkx->bskx", h, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"][None, None]
        k = k + p["bk"][None, None]
        v = v + p["bv"][None, None]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])

    local = mixer == "local" or (mixer == "hybrid" and cfg.window > 0)
    theta = cfg.rope_theta_local if local else cfg.rope_theta
    inv = rope_inv_freq(dh, theta)
    q = apply_rope(q, positions, inv)
    k = apply_rope(k, positions, inv)
    window = cfg.window if local else 0

    # Pallas fast path (TPU target; interpret mode off-TPU)
    if (rt.use_pallas and mode != "decode" and S % 128 == 0
            and paged is None):
        from repro.kernels import ops as kops
        out = kops.attention_bshd(q, k, v, causal=cfg.causal, window=window,
                                  cap=cfg.attn_softcap, use_pallas=True)
        new_cache = {}
        if mode == "prefill":
            if local:
                Wr = cache["k"].shape[1]
                ck, cv = kvc.prefill_fill_ring(cache["k"], cache["v"], k, v,
                                               Wr, lens)
            else:
                ck, cv = kvc.prefill_fill_slab(cache["k"], cache["v"], k, v)
            new_cache = {"k": ck, "v": cv}
        out = jnp.einsum("bshx,hxd->bsd", out, p["wo"])
        return out, new_cache

    q = q * (dh ** -0.5)

    new_cache: Dict = {}
    # ---- paged path: global-attn KV lives in a shared page pool ---- #
    if paged is not None and mixer == "global":
        from repro.models.attention import (attention_paged_decode,
                                            attention_paged_prefill,
                                            paged_write)
        bt = paged["block_tables"]                       # [B, nb]
        ps = cache["k_pages"].shape[2]
        nb = bt.shape[1]
        # rt.use_pallas routes the serving hot path through the ragged
        # Pallas kernels (interpret mode off-TPU, so CPU CI runs the
        # IDENTICAL kernel); the dense gather_pages implementations in
        # attention.py stay as the parity oracles, not the hot path.
        # q is already scaled by dh**-0.5 above, so the kernels get
        # scale=1.0.
        if mode == "decode":
            pos = positions[:, 0]                        # [B]
            page = jnp.take_along_axis(
                bt, jnp.minimum(pos // ps, nb - 1)[:, None], axis=1)[:, 0]
            ck = paged_write(cache["k_pages"], k[:, 0], page, pos % ps)
            cv = paged_write(cache["v_pages"], v[:, 0], page, pos % ps)
            if rt.use_pallas:
                from repro.kernels.ops import interpret_mode
                from repro.kernels.paged_attention import \
                    paged_decode_attention
                # true per-slot lengths: the engine's device-resident
                # ``pos`` buffer (SlotState.ctx_len mirror) — HBM reads
                # scale with live context, not the padded table width;
                # rows not decoding keep a frozen ``pos`` and read nothing
                lengths = pos + 1
                if "active" in paged:
                    lengths = jnp.where(paged["active"], lengths, 0)
                out = paged_decode_attention(
                    q[:, 0], ck, cv, bt, lengths, cap=cfg.attn_softcap,
                    scale=1.0, interpret=interpret_mode())[:, None]
            else:
                out = attention_paged_decode(q, ck, cv, bt, pos,
                                             cap=cfg.attn_softcap)
        else:                                            # prefill chunk
            offs0 = paged["q_offsets"]                   # [B]
            C = k.shape[1]
            if lens is None:
                lens = jnp.full((B,), C, jnp.int32)
            if rt.use_pallas:
                from repro.kernels.ops import interpret_mode
                from repro.kernels.paged_prefill import \
                    paged_prefill_attention
                out = paged_prefill_attention(
                    q, k, v, cache["k_pages"], cache["v_pages"], bt, offs0,
                    lens, cap=cfg.attn_softcap, scale=1.0,
                    interpret=interpret_mode())
            else:
                out = attention_paged_prefill(
                    q, k, v, cache["k_pages"], cache["v_pages"], bt, offs0,
                    lens, cap=cfg.attn_softcap)
            pos_grid = offs0[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
            validc = jnp.arange(C, dtype=jnp.int32)[None] < lens[:, None]
            pages = jnp.take_along_axis(
                bt, jnp.minimum(pos_grid // ps, nb - 1), axis=1)
            pages = jnp.where(validc, pages, kvc.GARBAGE_PAGE)
            n = B * C
            ck = paged_write(cache["k_pages"], k.reshape(n, K, dh),
                             pages.reshape(n), (pos_grid % ps).reshape(n))
            cv = paged_write(cache["v_pages"], v.reshape(n, K, dh),
                             pages.reshape(n), (pos_grid % ps).reshape(n))
        out = jnp.einsum("bshx,hxd->bsd", out, p["wo"])
        return out, {"k_pages": ck, "v_pages": cv}

    if mode == "decode":
        pos = positions[:, 0]                      # [B]
        Wr = cache["k"].shape[1]
        ck, cv = kvc.write_decode_kv(cache["k"], cache["v"], k, v, pos,
                                     ring=local, W=Wr)
        if local:
            kv_pos = kvc.ring_positions(pos + 1, Wr)
        else:
            kv_pos = kvc.slab_positions(pos + 1, Wr)
        out = attention_decode(q, ck, cv, kv_pos, pos,
                               window=window, cap=cfg.attn_softcap)
        new_cache = {"k": ck, "v": cv}
    else:
        out = attention_fwd(q, k, v, causal=cfg.causal, window=window,
                            cap=cfg.attn_softcap, q_block=rt.q_block)
        if mode == "prefill":
            if local:
                Wr = cache["k"].shape[1]
                ck, cv = kvc.prefill_fill_ring(cache["k"], cache["v"], k, v,
                                               Wr, lens)
            else:
                ck, cv = kvc.prefill_fill_slab(cache["k"], cache["v"], k, v)
            new_cache = {"k": ck, "v": cv}
    out = jnp.einsum("bshx,hxd->bsd", out, p["wo"])
    return out, new_cache


def _apply_layer(p, x, *, cfg: ModelConfig, rt: ModelRuntime, mixer: str,
                 mlp_kind: str, mode: str, cache, positions, seq_mask,
                 paged=None):
    new_cache: Dict = {}
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"]["scale"])

    attn_out = m_out = None
    if mixer in ("global", "local", "hybrid"):
        lens = (seq_mask.astype(jnp.int32).sum(-1)
                if (seq_mask is not None and mode == "prefill") else None)
        attn_out, kv_new = _attn_apply(p["attn"], h, cfg, rt, mixer, mode,
                                       cache, positions, lens=lens,
                                       paged=paged)
        new_cache.update(kv_new)
    if mixer in ("mamba", "hybrid"):
        if mode == "decode":
            m_out, mc = mamba_mixer_decode(
                p["mamba"], h[:, 0], cfg,
                {"conv": cache["conv"], "ssm": cache["ssm"]})
            m_out = m_out[:, None, :]
            new_cache.update(mc)
        else:
            if seq_mask is not None:
                h = h * seq_mask[..., None].astype(h.dtype)
            lens = (seq_mask.astype(jnp.int32).sum(-1)
                    if seq_mask is not None else None)
            if mode == "prefill":
                m_out, mc = mamba_mixer_fwd(p["mamba"], h, cfg,
                                            chunk=rt.ssd_chunk,
                                            return_state=True,
                                            seq_lens=lens)
                new_cache.update(mc)
            else:
                m_out = mamba_mixer_fwd(p["mamba"], h, cfg,
                                        chunk=rt.ssd_chunk, seq_lens=lens)

    if mixer == "hybrid":
        mix = 0.5 * (rms_norm(attn_out, p["attn_norm"]["scale"])
                     + rms_norm(m_out, p["ssm_norm"]["scale"]))
    elif mixer == "mamba":
        mix = m_out
    else:
        mix = attn_out
    if cfg.post_norms:
        mix = rms_norm(mix, p["post_ln1"]["scale"])
    x = rt.shard_act(x + mix)

    if mlp_kind != "none":
        h2 = rms_norm(x, p["ln2"]["scale"])
        if mlp_kind == "moe":
            mlp_out, aux = moe_layer(p["mlp"], h2, cfg, rt)
        else:
            mlp_out = apply_mlp(p["mlp"], h2)
        if cfg.post_norms:
            mlp_out = rms_norm(mlp_out, p["post_ln2"]["scale"])
        x = x + mlp_out
    x = rt.shard_act(x)
    return x, new_cache, aux


# --------------------------------------------------------------------------- #
# full model
# --------------------------------------------------------------------------- #
def forward(params, cfg: ModelConfig, rt: ModelRuntime, *, tokens=None,
            embeds=None, seq_mask=None, cache=None, mode: str = "train",
            paged=None):
    """Returns dict(hidden=[B,S,D] f-compute-dtype, cache=..., aux=scalar).

    train:   tokens [B,S] (or embeds [B,S,D]); cache must be None.
    prefill: like train but ``cache`` is a fresh cache to fill.
    decode:  tokens [B] int32; cache required; positions = cache["pos"].

    ``paged`` routes global-attn KV through shared page pools instead of
    per-slot slabs: {"block_tables": [B, nb] int32} plus, for prefill
    chunks, {"q_offsets": [B] int32} — the number of tokens each row already
    has in the pool (the chunk attends to that prefix and is written after),
    and for decode optionally {"active": [B] bool}: the Pallas kernel reads
    no context for inactive rows, whose outputs the caller discards.
    """
    assert mode in ("train", "prefill", "decode")
    if mode == "decode":
        assert cache is not None and tokens is not None
        x = embed_tokens(params["embed"], tokens[:, None], cfg.embed_scale,
                         cfg.d_model)
        positions = cache["pos"][:, None]          # [B,1]
    else:
        if embeds is not None:
            x = embeds.astype(dtype_of(cfg))
        else:
            x = embed_tokens(params["embed"], tokens, cfg.embed_scale,
                             cfg.d_model)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        if paged is not None and "q_offsets" in paged:
            positions = paged["q_offsets"][:, None] + positions
    x = rt.shard_act(x)

    mixers = cfg.layer_mixers()
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Dict = {"prefix": {}, "groups": {}, "suffix": {}}

    # ---- prefix layers (unrolled) ----
    for i in range(cfg.first_k_dense):
        lc = cache["prefix"][str(i)] if cache is not None else None
        x, nc, aux = _apply_layer(
            params["prefix"][str(i)], x, cfg=cfg, rt=rt, mixer=mixers[i],
            mlp_kind="dense", mode=mode, cache=lc, positions=positions,
            seq_mask=seq_mask, paged=paged)
        new_cache["prefix"][str(i)] = nc
        aux_total += aux

    # ---- scanned groups ----
    G = cfg.n_groups

    def group_body(carry, xs):
        xx, aux_acc = carry
        gp, gc = xs
        ncs = {}
        for j, mixer in enumerate(cfg.pattern):
            lc = gc.get(f"sub{j}") if gc else None
            xx, nc, a = _apply_layer(
                gp[f"sub{j}"], xx, cfg=cfg, rt=rt, mixer=mixer,
                mlp_kind=cfg.mlp_kind, mode=mode, cache=lc,
                positions=positions, seq_mask=seq_mask, paged=paged)
            ncs[f"sub{j}"] = nc
            aux_acc = aux_acc + a
        return (xx, aux_acc), ncs

    if G > 0:
        body = group_body
        if rt.remat and mode == "train":
            body = jax.checkpoint(group_body)
        gcaches = cache["groups"] if cache is not None else {}
        (x, aux_total), group_new = jax.lax.scan(
            body, (x, aux_total), (params["groups"], gcaches))
        new_cache["groups"] = group_new

    # ---- suffix layers (unrolled) ----
    base = cfg.first_k_dense + G * cfg.group_size
    for i, mixer in enumerate(cfg.suffix_pattern):
        lc = cache["suffix"][str(i)] if cache is not None else None
        x, nc, aux = _apply_layer(
            params["suffix"][str(i)], x, cfg=cfg, rt=rt, mixer=mixer,
            mlp_kind=cfg.mlp_kind, mode=mode, cache=lc, positions=positions,
            seq_mask=seq_mask, paged=paged)
        new_cache["suffix"][str(i)] = nc
        aux_total += aux

    x = rms_norm(x, params["final_norm"]["scale"])

    if mode == "train":
        return {"hidden": x, "cache": None, "aux": aux_total}
    # update position counter
    if mode == "decode":
        new_cache["pos"] = cache["pos"] + 1
    else:
        S = x.shape[1]
        if seq_mask is not None:
            new_cache["pos"] = seq_mask.astype(jnp.int32).sum(axis=-1)
        else:
            new_cache["pos"] = jnp.full((x.shape[0],), S, jnp.int32)
        if paged is not None and "q_offsets" in paged:
            new_cache["pos"] = paged["q_offsets"] + new_cache["pos"]
    return {"hidden": x, "cache": new_cache, "aux": aux_total}


# --------------------------------------------------------------------------- #
# logits / logprobs
# --------------------------------------------------------------------------- #
def unembed_matrix(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T            # [D, V]
    return params["lm_head"]


def logits_from_hidden(params, cfg: ModelConfig, hidden):
    """hidden [..., D] -> logits [..., V] (f32, softcapped)."""
    w = unembed_matrix(params, cfg)
    logits = jnp.einsum("...d,dv->...v", hidden, w).astype(jnp.float32)
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    return logits


def token_logprobs(params, cfg: ModelConfig, hidden, targets,
                   block: int = 512, rt: ModelRuntime = CPU_RT):
    """Per-token log p(target) without materialising [B,S,V] logits.

    hidden: [B,S,D], targets: [B,S] int32 -> [B,S] f32.
    """
    B, S, D = hidden.shape
    if S <= block:
        logits = logits_from_hidden(params, cfg, hidden)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return tgt - lse

    pad = (-S) % block
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        return token_logprobs(params, cfg, hidden, targets, block, rt)[:, :S]
    n = S // block
    hs = hidden.reshape(B, n, block, D).swapaxes(0, 1)
    ts = targets.reshape(B, n, block).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        h, t = args
        h = rt.shard_act(h)
        logits = logits_from_hidden(params, cfg, h)
        logits = rt.shard_act(logits, None, rt.model_axis)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return tgt - lse

    out = jax.lax.map(one, (hs, ts))        # [n, B, block]
    return out.swapaxes(0, 1).reshape(B, S)


# --------------------------------------------------------------------------- #
# convenience entry points
# --------------------------------------------------------------------------- #
def prefill(params, cfg, rt, tokens=None, embeds=None, seq_mask=None,
            cache=None, slab_len=None, cache_dtype=jnp.bfloat16):
    if cache is None:
        x = tokens if tokens is not None else embeds
        B = x.shape[0]
        slab = slab_len or x.shape[1]
        cache = kvc.init_cache(cfg, B, slab, cache_dtype)
    return forward(params, cfg, rt, tokens=tokens, embeds=embeds,
                   seq_mask=seq_mask, cache=cache, mode="prefill")


def decode_step(params, cfg, rt, tokens, cache, paged=None):
    return forward(params, cfg, rt, tokens=tokens, cache=cache, mode="decode",
                   paged=paged)
