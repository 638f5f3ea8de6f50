"""Model configuration and family presets."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Static hyperparameters of a decoder-only LM.

    ``family`` picks the architectural switches; everything else is sized
    explicitly so tiny test/dryrun configs and real configs share one code
    path (static shapes only — required for XLA).
    """
    # gpt2 | llama | mixtral | deepseek_v32 | lfm2_moe | ouro
    family: str = "llama"
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8              # == n_heads for MHA (gpt2)
    d_ff: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    # architecture switches (derived from family by get_config)
    use_rope: bool = True            # else learned positional embedding
    use_rmsnorm: bool = True         # else LayerNorm with bias
    use_swiglu: bool = True          # else GeLU MLP
    tie_embeddings: bool = False
    # mixture of experts (0 experts = dense)
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: str = "bfloat16"          # activations/params compute dtype
    # ---- family "deepseek_v32" (serving only): latent attention (MLA)
    # with a learned sparse selection, leading dense layers before the
    # expert layers, a sigmoid group-limited router wider than the
    # experts this replica holds, and YaRN frequencies. ``n_experts`` is
    # then the number of routed experts HELD here (``experts_from ..
    # experts_from + n_experts`` of the router's ``n_routed_experts``),
    # ``d_ff`` the dense layers' MLP width and ``d_ff_expert`` an expert's.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    n_dense_layers: int = 0          # leading layers with a dense MLP
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    n_routed_experts: int = 0        # the router's width
    experts_from: int = 0            # first routed expert held here
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    rope_factor: float = 1.0         # YaRN; 1.0 = plain theta
    rope_original_max_seq: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    # ---- family "lfm2_moe" (serving only): every layer is an operator
    # (``layer_types[l]``: "conv", a gated short convolution whose
    # recurrent state is its last ``conv_kernel - 1`` inputs, or
    # "full_attention", GQA with a per-head RMSNorm of q and k) followed
    # by a SwiGLU FFN (``d_ff`` wide in the first ``n_dense_layers``
    # layers, after them a sigmoid top-k router with a choice-only bias
    # over ``n_routed_experts`` experts ``d_ff_expert`` wide, every one
    # resident, no shared expert). The chosen weights are normalised by
    # their sum plus ``router_norm_eps``. Embedding and head are tied.
    layer_types: Tuple[str, ...] = ()
    conv_kernel: int = 0
    router_norm_eps: float = 0.0
    # ---- family "ouro" (serving only): the llama layer with a norm
    # after each sub-layer as well as before it (``sandwich_norm``:
    # ``x += norm(Attn(norm(x))); x += norm(MLP(norm(x)))``), and the
    # SAME ``n_layers`` layers run ``n_passes`` times a token over one
    # set of weights, the final norm closing every pass. Each pass
    # caches its own K and V: a token keeps ``n_passes * n_layers``
    # entries, pass ``t`` (from 0) of layer ``l`` in slot ``t * n_layers
    # + l``. An exit gate (two leaves of the tree) gives every pass a
    # probability; at ``early_exit_threshold`` 1.0, the published value
    # and the only one built, every token takes the last pass.
    n_passes: int = 1
    sandwich_norm: bool = False
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        if self.family == "deepseek_v32":
            _validate_deepseek_v32(self)
        elif self.family == "lfm2_moe":
            _validate_lfm2_moe(self)
        elif self.family == "ouro":
            _validate_ouro(self)
        elif self.n_passes != 1 or self.sandwich_norm:
            raise ValueError(
                f"family={self.family!r}: n_passes and sandwich_norm are "
                "family 'ouro''s (no other family's training or serving "
                "layer reads them)")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0


def _validate_deepseek_v32(c: ModelConfig) -> None:
    """Every field the family needs, checked where the config is made:
    a missing width must not surface as a shape error inside a jit."""
    def need(ok: bool, what: str) -> None:
        if not ok:
            raise ValueError(f"family='deepseek_v32': {what}")
    for name in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                 "qk_rope_head_dim", "v_head_dim", "index_n_heads",
                 "index_head_dim", "index_topk", "d_ff_expert",
                 "n_routed_experts", "n_experts"):
        need(getattr(c, name) > 0, f"{name} must be set (> 0)")
    need(c.qk_rope_head_dim % 2 == 0 and
         c.index_head_dim > c.qk_rope_head_dim,
         "qk_rope_head_dim must be even and below index_head_dim (the "
         "index key is a rotary part followed by a plain one)")
    need(0 <= c.n_dense_layers <= c.n_layers,
         f"n_dense_layers={c.n_dense_layers} outside 0..n_layers")
    need(0 <= c.experts_from and
         c.experts_from + c.n_experts <= c.n_routed_experts,
         f"experts_from={c.experts_from} + n_experts={c.n_experts} "
         f"exceeds n_routed_experts={c.n_routed_experts}")
    need(c.n_routed_experts % c.n_group == 0 and
         1 <= c.topk_group <= c.n_group,
         f"n_group={c.n_group} must divide n_routed_experts and "
         f"topk_group={c.topk_group} lie in 1..n_group")
    need(c.top_k <= c.topk_group * (c.n_routed_experts // c.n_group),
         f"top_k={c.top_k} exceeds the experts of topk_group groups")
    need(c.n_routed_experts // c.n_group >= 2,
         "a group needs two experts (its score is the sum of its two "
         "largest)")
    need(c.use_rope and c.use_rmsnorm and c.use_swiglu
         and not c.tie_embeddings,
         "RoPE, RMSNorm, SwiGLU and an untied head are the architecture")
    need(c.rope_factor == 1.0 or c.rope_original_max_seq > 0,
         "rope_original_max_seq must be set when rope_factor != 1")


LFM2_OPERATORS = ("conv", "full_attention")


def _validate_lfm2_moe(c: ModelConfig) -> None:
    def need(ok: bool, what: str) -> None:
        if not ok:
            raise ValueError(f"family='lfm2_moe': {what}")
    need(len(c.layer_types) == c.n_layers,
         f"layer_types names {len(c.layer_types)} layers, n_layers is "
         f"{c.n_layers}")
    need(all(t in LFM2_OPERATORS for t in c.layer_types),
         f"layer_types holds {sorted(set(c.layer_types))}; an operator is "
         f"one of {LFM2_OPERATORS}")
    need(0 <= c.n_dense_layers <= c.n_layers,
         f"n_dense_layers={c.n_dense_layers} outside 0..n_layers")
    need(c.conv_kernel >= 2, "conv_kernel (conv_L_cache) must be >= 2")
    need(c.d_model % c.n_heads == 0 and c.n_heads % c.n_kv_heads == 0
         and c.head_dim % 2 == 0,
         f"d_model={c.d_model} must divide into n_heads={c.n_heads} even "
         f"heads and n_kv_heads={c.n_kv_heads} must divide n_heads")
    if c.n_layers > c.n_dense_layers:
        need(c.d_ff_expert > 0 and c.n_experts > 0,
             "d_ff_expert and n_experts must be set (> 0)")
        need(c.n_routed_experts >= c.n_experts and 0 <= c.experts_from
             and c.experts_from + c.n_experts <= c.n_routed_experts,
             f"experts_from={c.experts_from} + n_experts={c.n_experts} "
             f"exceeds n_routed_experts={c.n_routed_experts}")
        need(c.n_group == 1 and c.topk_group == 1
             and c.n_shared_experts == 0,
             "one router group and no shared expert are the architecture")
        need(2 <= c.top_k <= c.n_routed_experts,
             f"top_k={c.top_k} outside 2..n_routed_experts")
    need(c.use_rope and c.use_rmsnorm and c.use_swiglu
         and c.tie_embeddings,
         "RoPE, RMSNorm, SwiGLU and a tied head are the architecture")


def _validate_ouro(c: ModelConfig) -> None:
    def need(ok: bool, what: str) -> None:
        if not ok:
            raise ValueError(f"family='ouro': {what}")
    need(c.n_passes >= 1, f"n_passes={c.n_passes} (total_ut_steps) must "
         "be >= 1")
    need(c.sandwich_norm, "sandwich_norm (a norm after each sub-layer "
         "too) is the architecture")
    need(c.d_model % c.n_heads == 0 and c.n_heads % c.n_kv_heads == 0
         and c.head_dim % 2 == 0,
         f"d_model={c.d_model} must divide into n_heads={c.n_heads} even "
         f"heads and n_kv_heads={c.n_kv_heads} must divide n_heads")
    need(c.use_rope and c.use_rmsnorm and c.use_swiglu
         and not c.tie_embeddings and not c.is_moe,
         "RoPE, RMSNorm, a dense SwiGLU MLP and an untied head are the "
         "architecture")
    if c.early_exit_threshold != 1.0:
        # a row that leaves the loop after fewer passes is another
        # program (rows of one step at different depths), not a setting
        raise NotImplementedError(
            f"family='ouro': early_exit_threshold="
            f"{c.early_exit_threshold} is not built: below 1.0 a token "
            "stops after the first pass whose cumulative exit "
            "probability reaches it, and only the published 1.0 (every "
            "token takes all n_passes) has a path")


# families with a serving path only, and what a training entry point
# would have to have for them
SERVING_ONLY = {
    "deepseek_v32": "latent attention, sparse selection or held-expert "
                    "layer",
    "lfm2_moe": "gated short convolution, per-head q/k norm or "
                "resident-expert layer",
    "ouro": "pass loop over one set of weights, sandwich norm or loss "
            "over exit passes"}


def refuse_training(cfg: ModelConfig, where: str) -> None:
    """Training entry points call this first: these families have a
    serving path only, and must never fall through to the llama layer."""
    if cfg.family in SERVING_ONLY:
        raise NotImplementedError(
            f"family={cfg.family!r} is built for serving only "
            f"(serving.engine.DecodeEngine); {where} has no "
            f"{SERVING_ONLY[cfg.family]}")


def _gpt2(**kw) -> ModelConfig:
    base = dict(family="gpt2", use_rope=False, use_rmsnorm=False,
                use_swiglu=False, tie_embeddings=True, norm_eps=1e-5)
    base.update(kw)
    return ModelConfig(**base)


PRESETS = {
    # smoke-test scale (CPU-runnable; cf. BASELINE.json config #1)
    "gpt2-125m": _gpt2(vocab_size=50257, d_model=768, n_layers=12,
                       n_heads=12, n_kv_heads=12, d_ff=3072, max_seq=1024),
    "llama3-8b": ModelConfig(family="llama", vocab_size=128256, d_model=4096,
                             n_layers=32, n_heads=32, n_kv_heads=8,
                             d_ff=14336, max_seq=8192),
    "llama3-70b": ModelConfig(family="llama", vocab_size=128256, d_model=8192,
                              n_layers=80, n_heads=64, n_kv_heads=8,
                              d_ff=28672, max_seq=8192),
    "gpt3-13b": _gpt2(vocab_size=50257, d_model=5120, n_layers=40,
                      n_heads=40, n_kv_heads=40, d_ff=20480, max_seq=2048),
    "mixtral-8x7b": ModelConfig(family="mixtral", vocab_size=32000,
                                d_model=4096, n_layers=32, n_heads=32,
                                n_kv_heads=8, d_ff=14336, max_seq=8192,
                                n_experts=8, top_k=2, rope_theta=1e6),
    # flagship for single-chip bench/entry: llama-style ~420M that fits
    # one v5e chip with optimizer state
    "flagship-420m": ModelConfig(family="llama", vocab_size=32768,
                                 d_model=1024, n_layers=24, n_heads=16,
                                 n_kv_heads=8, d_ff=2816, max_seq=2048,
                                 rope_theta=500000.0),
    # wider flagship (~1B): d_model 2048 lifts the single-chip MXU
    # ceiling from ~0.74 (d=1024 contractions) to ~0.90 measured on the
    # v5e; sized so params+grads+fp32 AdamW moments (~12 GB) plus
    # full-remat activations still fit 15.75 GB HBM
    "flagship-1b": ModelConfig(family="llama", vocab_size=32768,
                               d_model=2048, n_layers=18, n_heads=16,
                               n_kv_heads=8, d_ff=5632, max_seq=2048,
                               rope_theta=500000.0),
    # tiny configs for tests and the multi-chip dryrun
    "tiny": ModelConfig(family="llama", vocab_size=256, d_model=64,
                        n_layers=4, n_heads=4, n_kv_heads=2, d_ff=128,
                        max_seq=128, dtype="float32", rope_theta=10000.0),
    "tiny-moe": ModelConfig(family="mixtral", vocab_size=256, d_model=64,
                            n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
                            max_seq=128, n_experts=4, top_k=2,
                            dtype="float32", rope_theta=10000.0),
    # the latent-attention / sparse-selection / held-experts family at
    # test size: 1 dense + 2 expert layers, 8 of 32 routed experts held
    "tiny-dsv32": ModelConfig(
        family="deepseek_v32", vocab_size=256, d_model=64, n_layers=3,
        n_heads=4, n_kv_heads=1, d_ff=128, max_seq=256, norm_eps=1e-6,
        rope_theta=10000.0, dtype="float32", n_experts=8, top_k=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
        index_head_dim=16, index_topk=16, n_dense_layers=1,
        d_ff_expert=32, n_shared_experts=1, n_routed_experts=32,
        experts_from=0, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, rope_factor=40.0,
        rope_original_max_seq=32, rope_mscale=1.0),
    # a gated short convolution in 5 of 7 layers, QK-normed GQA in 2,
    # 1 dense FFN then a sigmoid top-4 router over 16 resident experts
    "tiny-lfm2": ModelConfig(
        family="lfm2_moe", vocab_size=256, d_model=64, n_layers=7,
        n_heads=4, n_kv_heads=2, d_ff=128, max_seq=256, norm_eps=1e-5,
        rope_theta=10000.0, dtype="float32", tie_embeddings=True,
        n_experts=16, n_routed_experts=16, top_k=4, d_ff_expert=32,
        n_dense_layers=1, conv_kernel=3, router_norm_eps=1e-6,
        layer_types=("conv", "full_attention", "conv", "conv",
                     "full_attention", "conv", "conv")),
    # 3 sandwich-normed layers run 3 times over: 9 K/V slots a token
    "tiny-ouro": ModelConfig(
        family="ouro", vocab_size=256, d_model=64, n_layers=3, n_heads=4,
        n_kv_heads=4, d_ff=128, max_seq=256, norm_eps=1e-6,
        rope_theta=10000.0, dtype="float32", n_passes=3,
        sandwich_norm=True),
    "tiny-gpt2": _gpt2(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                       n_kv_heads=4, d_ff=256, max_seq=128, dtype="float32"),
}


def get_config(name: str, **overrides) -> ModelConfig:
    cfg = PRESETS[name]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg
