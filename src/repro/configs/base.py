"""Config schema: ModelConfig (architecture) + InputShape (workload cell).

Every assigned architecture provides a module ``repro.configs.<id>`` exposing
``CONFIG`` (the exact published config) and ``SMOKE`` (a reduced same-family
config runnable on one CPU).  ``repro.configs.registry`` maps ids to both.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Tuple

__all__ = ["ModelConfig", "InputShape", "LM_SHAPES", "shape_by_name"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str = "model"
    family: str = "dense"   # dense | moe | ssm | hybrid | encdec | vlm
    # trunk
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    head_dim: int = 0                 # 0 -> d_model // num_heads
    activation: str = "swiglu"        # swiglu | gelu | relu2
    norm: str = "rmsnorm"             # rmsnorm | layernorm
    norm_eps: float = 1e-6
    positional: str = "rope"          # rope | learned | none
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    qk_norm: bool = False             # qwen3-style per-head q/k RMSNorm
    # MoE
    num_experts: int = 0
    top_k: int = 0
    moe_dff: int = 0
    dense_residual: bool = False      # arctic: dense FFN in parallel with MoE
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # SSM / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    conv_bias: bool = False           # mamba2: bias on the depthwise conv
    ssm_chunk: int = 128              # mamba2 prefill: SSD chunk length
    attn_every: int = 0               # zamba2: shared attn block every N blocks
    # granite-4.0-h / jamba: one attention layer with its own weights per
    # ``attn_period`` layers, at index ``attn_offset`` of the period; every
    # other layer is mamba2, and an MLP follows every mixer
    attn_period: int = 0
    attn_offset: int = 0
    slstm_every: int = 0              # xlstm: one sLSTM per this many blocks
    # muP multipliers (granite): embeddings x embed_scale, every residual
    # branch x residual_scale, attention softmax scale attn_scale (0 ->
    # 1/sqrt(head_dim)), logits x logits_scale; the defaults change nothing
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float = 0.0
    logits_scale: float = 1.0
    # enc-dec
    encoder_layers: int = 0
    encoder_seq: int = 0              # whisper: 1500 precomputed frames (stub)
    # vlm
    cross_attn_every: int = 0         # one cross-attn layer per this many
    num_image_tokens: int = 0         # precomputed patch embeddings (stub)
    # parallelism profile: "tp" = FSDP+TP(+EP) (Megatron-style; required for
    # the 90B+ and MoE archs); "dp" = ZeRO-3-style pure data parallel with
    # fully-sharded params (no TP activation all-reduces) — the right choice
    # for <=30B dense/ssm archs on 256+ chips (EXPERIMENTS.md §Perf it.2).
    # Applies to train cells; serving always uses "tp".
    parallelism: str = "tp"
    # numerics / execution
    dtype: str = "bfloat16"
    remat: bool = True
    sequence_parallel: bool = False   # shard residual activations over model
    attn_chunk: int = 1024            # blockwise-attention chunk (prefill/train)
    attn_impl: str = "flash"          # flash | masked (see §Perf)
    # pin batch-only (replicated-head) layout on flash-loop tensors when
    # kv_heads doesn't divide TP: big win for deep/microbatched archs
    # (nemotron 3x), slightly negative for arctic (no microbatching) — §Perf
    flash_replicate_pin: bool = True
    # explicit Megatron-SP activation gather before TP matmuls: required for
    # big-dense archs (nemotron: stops full-weight gathers, 4x), harmful for
    # the MoE archs whose shard_map/flash layouts reshard better unaided
    sp_matmul_gather: bool = True
    # int8 KV cache (dense/moe families): kneads the *cache* the same way
    # weights are kneaded — per-(position, head) scale, 2x decode cache bytes
    kv_cache_bits: int = 0            # 0 = bf16, 8 = int8
    # SAC execution path for KneadedWeight projection leaves (the kneaded
    # LM serving form, docs/DESIGN.md §7): "float" | "int" | "planes" |
    # "pallas".  Float-weight leaves ignore it, so training configs can
    # leave the default; ServingEngine overrides it to match its impl.
    # (Canonical name ``impl`` — the same switch the serving configs use;
    # ``sac_impl=`` is accepted as a deprecated constructor/replace alias,
    # consumed by __post_init__ and normalized back to None so a later
    # ``dataclasses.replace(cfg, impl=...)`` can never be overridden by a
    # stale copied alias.  Read sites must use ``cfg.impl``.)
    impl: str = "int"
    sac_impl: Optional[str] = dataclasses.field(default=None, repr=False,
                                                compare=False)
    # Runtime activation-side skip for KneadedWeight leaves (two-sided skip,
    # docs/DESIGN.md §12): intersect per-K-tile activation presence into the
    # kernel's schedule walk on decode-GEMV calls (<= 8 flattened rows);
    # prefill falls back to the static weight-only skip.  Bit-exact on/off —
    # dropped work items contribute exactly 0.0.  Float-weight leaves and
    # the non-pallas impls ignore it; ServingEngine overrides it from
    # ``ServingConfig.activation_skip``.
    activation_skip: bool = False
    window: int = 0                   # >0: sliding-window attention (long ctx)
    # training
    microbatch: int = 0               # 0 -> no gradient accumulation

    def __post_init__(self) -> None:
        if self.attn_period and (
                self.num_layers % self.attn_period
                or not 0 <= self.attn_offset < self.attn_period):
            raise ValueError(
                f"{self.name!r}: num_layers={self.num_layers} must be whole "
                f"periods of attn_period={self.attn_period}, with "
                f"attn_offset={self.attn_offset} inside the period")
        if self.sac_impl is not None:
            warnings.warn(
                "ModelConfig.sac_impl is deprecated; use impl=",
                DeprecationWarning, stacklevel=3)
            object.__setattr__(self, "impl", self.sac_impl)
            object.__setattr__(self, "sac_impl", None)
        if self.num_experts:
            if not (0 < self.top_k <= self.num_experts):
                raise ValueError(
                    f"{self.name!r}: top_k={self.top_k} must be in "
                    f"[1, num_experts={self.num_experts}]")
            if self.moe_dff <= 0 and self.d_ff <= 0:
                raise ValueError(
                    f"{self.name!r}: MoE config needs moe_dff (or d_ff) > 0")
            if self.capacity_factor <= 0:
                raise ValueError(
                    f"{self.name!r}: capacity_factor must be > 0, "
                    f"got {self.capacity_factor}")

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def full_attention(self) -> bool:
        """True if the arch relies on (windowless) softmax attention."""
        return self.family not in ("ssm",) and self.window == 0

    def param_count(self) -> int:
        """Analytic parameter count (embedding + trunk), for 6ND roofline."""
        d, f, v, hd = self.d_model, self.d_ff, self.vocab_size, self.hd
        nh, nkv = self.num_heads, self.num_kv_heads
        attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
        if self.activation == "swiglu":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        per_layer = attn + (mlp if f else 0)
        if self.num_experts:
            e_f = self.moe_dff or f
            moe = self.num_experts * 3 * d * e_f + d * self.num_experts
            per_layer = attn + moe + (mlp if self.dense_residual and f else 0)
        if self.family == "ssm":
            di = self.ssm_expand * d
            per_layer = 2 * (d * 2 * di + di * d)     # coarse mLSTM/sLSTM proj
        if self.family == "hybrid":
            di = self.ssm_expand * d
            per_layer = d * 2 * di + di * d + 2 * di * self.ssm_state
        n = self.num_layers * per_layer + v * d * (1 if self.tie_embeddings else 2)
        if self.family == "hybrid" and self.attn_period:
            di, ns = self.ssm_expand * d, self.ssm_state
            mamba = d * (2 * di + 2 * ns + di // self.ssm_head_dim) + di * d
            n_attn = self.num_layers // self.attn_period
            n += (n_attn * (attn + mlp) + (self.num_layers - n_attn)
                  * (mamba + mlp) - self.num_layers * per_layer)
        if self.encoder_layers:
            n += self.encoder_layers * (attn + (mlp if f else 0))
        if self.cross_attn_every:
            n_cross = self.num_layers // self.cross_attn_every
            n += n_cross * (attn)
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k experts only)."""
        if not self.num_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        e_f = self.moe_dff or f
        dense = self.param_count() - self.num_layers * self.num_experts * 3 * d * e_f
        return dense + self.num_layers * self.top_k * 3 * d * e_f


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One workload cell: (kind, seq_len, global_batch)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


LM_SHAPES: Tuple[InputShape, ...] = (
    InputShape("train_4k", 4_096, 256, "train"),
    InputShape("prefill_32k", 32_768, 32, "prefill"),
    InputShape("decode_32k", 32_768, 128, "decode"),
    InputShape("long_500k", 524_288, 1, "decode"),
)


def shape_by_name(name: str) -> InputShape:
    for s in LM_SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)
