"""Model configuration system.

Every assigned architecture is expressed as a :class:`ModelConfig`.  The
transformer in ``repro.models.transformer`` consumes these configs and builds a
scan-over-layer-groups model, so heterogeneous layer patterns (gemma's 5:1
local:global, deepseek's first-dense-layer) remain scan friendly.

Conventions
-----------
* ``pattern`` is the repeating *group* of mixer kinds.  ``n_layers -
  first_k_dense`` must be divisible by ``len(pattern)``; the model scans over
  ``n_groups = (n_layers - first_k_dense) // len(pattern)`` groups.
* ``first_k_dense`` prefix layers (deepseek-moe) are unrolled before the scan
  and always use a dense MLP of width ``d_ff_dense_prefix``.
* ``input_mode`` is ``"tokens"`` for LM archs and ``"embeds"`` for modality
  backbones whose frontend is stubbed (hubert frames / llava patches) — the
  model then consumes precomputed ``(B, S, d_model)`` embeddings.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, Tuple

MIXER_KINDS = ("global", "local", "mamba", "hybrid")
MLP_KINDS = ("dense", "moe", "none")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- attention ----------------------------------------------------------
    pattern: Tuple[str, ...] = ("global",)
    # logical head padding (beyond-paper §Perf optimization): pad q-heads to
    # a TP-divisible count; padded heads have zero output rows, so the model
    # is mathematically identical while attention shards on the model axis.
    pad_heads: int = 0              # 0 = no padding; else padded H
    window: int = 0                 # sliding-window size for "local" mixers
    qkv_bias: bool = False
    qk_norm: bool = False
    attn_softcap: float = 0.0       # gemma2-style attention logit soft cap
    final_softcap: float = 0.0      # gemma2-style final logit soft cap
    post_norms: bool = False        # gemma2 post-attention/post-ffn RMSNorms
    rope_theta: float = 1.0e4
    rope_theta_local: float = 1.0e4
    causal: bool = True             # False => encoder-only (hubert)
    embed_scale: bool = False       # gemma multiplies embeddings by sqrt(d)
    tie_embeddings: bool = True

    # trailing layers that do not fill a whole pattern group are unrolled
    # after the scan (gemma3-4b: 34 layers = 5 full (5L+1G) groups + 4 local)
    suffix_pattern: Tuple[str, ...] = ()

    # --- mlp ----------------------------------------------------------------
    mlp_kind: str = "dense"         # dense | moe | none
    first_k_dense: int = 0
    d_ff_dense_prefix: int = 0

    # --- moe ----------------------------------------------------------------
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 1.0e-2
    shared_expert_gate: bool = False  # qwen2-moe sigmoid gate on shared experts

    # --- ssm (mamba-2 / SSD) -------------------------------------------------
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_groups: int = 1

    # --- io -----------------------------------------------------------------
    input_mode: str = "tokens"      # tokens | embeds
    dtype: str = "bfloat16"

    # ------------------------------------------------------------------------
    def __post_init__(self):
        assert self.family in ("dense", "moe", "ssm", "hybrid", "audio", "vlm")
        assert self.mlp_kind in MLP_KINDS
        for m in self.pattern + self.suffix_pattern:
            assert m in MIXER_KINDS, m
        scanned = self.n_layers - self.first_k_dense - len(self.suffix_pattern)
        assert scanned % len(self.pattern) == 0, (
            f"{self.name}: {scanned} scanned layers not divisible by "
            f"pattern length {len(self.pattern)}")
        if self.mlp_kind == "moe":
            assert self.n_experts > 0 and self.top_k > 0 and self.d_ff_expert > 0
        if any(m in ("mamba", "hybrid") for m in self.pattern):
            assert self.ssm_state > 0

    # --- derived ------------------------------------------------------------
    @property
    def n_groups(self) -> int:
        return ((self.n_layers - self.first_k_dense - len(self.suffix_pattern))
                // len(self.pattern))

    @property
    def group_size(self) -> int:
        return len(self.pattern)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def n_heads_eff(self) -> int:
        """Head count actually materialized (>= n_heads when pad_heads set).
        Padded heads live at the tail of each GQA group."""
        if self.pad_heads:
            assert self.pad_heads >= self.n_heads
            assert self.pad_heads % max(self.n_kv_heads, 1) == 0
            return self.pad_heads
        return self.n_heads

    @property
    def d_inner(self) -> int:
        """Mamba inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def conv_dim(self) -> int:
        # conv runs over the concatenated [x, B, C] channels (mamba-2 layout)
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def n_experts_padded(self) -> int:
        """Experts padded to a multiple of 16 so expert-parallel shard_map
        divides on any model-axis size up to 16 (padded experts get -inf
        router logits and are never selected)."""
        if self.n_experts == 0:
            return 0
        return ((self.n_experts + 15) // 16) * 16

    @property
    def has_attention(self) -> bool:
        return any(m in ("global", "local", "hybrid") for m in self.pattern)

    @property
    def has_ssm(self) -> bool:
        return any(m in ("mamba", "hybrid") for m in self.pattern)

    @property
    def is_decoder(self) -> bool:
        """Whether the arch supports autoregressive decode."""
        return self.causal

    @property
    def sub_quadratic(self) -> bool:
        """True when decode-memory is O(1)/O(window) per token (long_500k ok)."""
        return all(m in ("mamba", "local", "hybrid")
                   for m in self.pattern + self.suffix_pattern)

    def layer_mixers(self) -> Tuple[str, ...]:
        """Mixer kind for every layer, in order."""
        base = "global" if self.has_attention else self.pattern[0]
        out = [base] * self.first_k_dense
        out += list(self.pattern) * self.n_groups
        out += list(self.suffix_pattern)
        return tuple(out)

    def mlp_kind_for_layer(self, layer_idx: int) -> str:
        if layer_idx < self.first_k_dense:
            return "dense"
        return self.mlp_kind

    def param_count(self) -> int:
        """Approximate parameter count (embeddings included once if tied)."""
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        total = V * D  # embeddings
        if not self.tie_embeddings:
            total += V * D
        mixers = self.layer_mixers()
        for li in range(self.n_layers):
            mix = mixers[li]
            if mix in ("global", "local", "hybrid"):
                H, K, dh = self.n_heads, self.n_kv_heads, self.head_dim
                total += D * (H + 2 * K) * dh + H * dh * D
            if mix in ("mamba", "hybrid"):
                din = self.d_inner
                d_in_proj = 2 * din + 2 * self.ssm_groups * self.ssm_state + self.ssm_nheads
                total += D * d_in_proj + din * D
                total += self.ssm_conv * self.conv_dim + self.conv_dim
                total += 3 * self.ssm_nheads + din
            kind = self.mlp_kind_for_layer(li)
            if kind == "dense":
                f = self.d_ff_dense_prefix if li < self.first_k_dense else F
                total += 3 * D * f
            elif kind == "moe":
                total += self.n_experts * 3 * D * self.d_ff_expert
                total += self.n_shared_experts * 3 * D * self.d_ff_expert
                total += D * self.n_experts
            total += 2 * D  # norms
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE counts top_k + shared experts only)."""
        if self.mlp_kind != "moe":
            return self.param_count()
        full = self.param_count()
        n_moe_layers = self.n_layers - self.first_k_dense
        inactive = (self.n_experts - self.top_k) * 3 * self.d_model * self.d_ff_expert
        return full - n_moe_layers * inactive

    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        small: Dict = dict(
            n_layers=(self.first_k_dense + 2 * self.group_size
                      + len(self.suffix_pattern)),
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, min(self.n_kv_heads, 2)),
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab_size=128,
            window=min(self.window, 16) if self.window else 0,
            d_ff_dense_prefix=128 if self.first_k_dense else 0,
            dtype="float32",
        )
        if self.mlp_kind == "moe":
            small.update(n_experts=8, top_k=min(self.top_k, 2), d_ff_expert=32,
                         n_shared_experts=min(self.n_shared_experts, 1))
        if self.has_ssm:
            small.update(ssm_state=16, ssm_headdim=16, ssm_expand=2, ssm_groups=1)
        small.update(overrides)
        small.setdefault("name", self.name + "-smoke")
        return dataclasses.replace(self, **small)


# --------------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------------- #
_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], ModelConfig]):
        _REGISTRY[name] = fn
        return fn
    return deco


def depth_cut(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """``cfg`` at its published widths with only the first ``n_layers``
    layers (whole pattern groups) — how a config is cut to fit a chip."""
    return dataclasses.replace(cfg, n_layers=n_layers,
                               name=f"{cfg.name}-{n_layers}L")


def padded_variant(cfg: ModelConfig, axis: int = 16):
    """Smallest logical head padding making n_heads divisible by the model
    axis while preserving GQA grouping.  Returns cfg unchanged if already
    divisible or if padding would exceed 2x the head count."""
    H, K = cfg.n_heads, max(cfg.n_kv_heads, 1)
    if H == 0 or (H % axis == 0):
        return cfg
    Hp = H + 1
    while Hp <= 2 * H:
        if Hp % K == 0 and Hp % axis == 0:
            return dataclasses.replace(cfg, pad_heads=Hp)
        Hp += 1
    return cfg


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        # late import of the arch modules so the registry is populated
        from repro.configs import ALL_ARCHS  # noqa: F401
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs():
    from repro.configs import ALL_ARCHS  # noqa: F401
    return sorted(_REGISTRY)
