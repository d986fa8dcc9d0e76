"""Architecture configuration: one frozen dataclass describes every model in
the zoo (dense / MoE / SSM / hybrid / encoder-only / VLM-backbone).

A model is a stack of ``n_layers`` layers organized as ``n_layers / len(period)``
repeating *periods*.  ``period[i]`` names the token mixer of position ``i``
("attn" or "ssm"); ``mlp_pattern[i]`` names its channel mixer ("mlp", "moe"
or "none").  Homogeneous models use a period of length 1; Jamba's 1:7
attention:Mamba interleave with MoE every other layer is a period of 8.
Scanning over periods keeps compile time O(period) instead of O(depth).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab: int
    n_kv_heads: int = 0  # 0 => MHA (== n_heads)
    head_dim: int = 0  # 0 => d_model // n_heads

    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0  # experts the router scores
    top_k: int = 0
    capacity_factor: float = 1.25  # 0 => dropless: every routed row is computed
    router: str = "softmax"  # softmax (capacity layer) | sigmoid_bias (dropless layer)
    routed_scale: float = 1.0  # combine weights of the routed experts are scaled by this
    shared_expert_ff: int = 0  # width of one shared expert every token passes (0 = none)
    experts_held: int = 0  # experts whose weights this device holds (0 = all of them)

    # --- attention ----------------------------------------------------------
    window: Optional[int] = None  # sliding-window size (None = full)
    causal: bool = True  # False => bidirectional encoder
    rope_theta: float = 10_000.0
    attn_chunk: int = 1024  # kv-chunk for flash-style chunked attention
    qkv_bias: bool = False
    pos_emb: str = "rope"  # rope | none

    # --- SSM (Mamba-2 / SSD) --------------------------------------------------
    ssm_state: int = 0  # N
    ssm_expand: int = 2
    ssm_n_heads: int = 0  # 0 => ssm_expand * d_model / ssm_head_dim
    ssm_head_dim: int = 64  # P
    ssm_groups: int = 1  # G
    ssm_conv: int = 4
    ssm_chunk: int = 128  # SSD chunk length Q

    # --- layer pattern --------------------------------------------------------
    period: Tuple[str, ...] = ("attn",)
    mlp_pattern: Tuple[str, ...] = ("mlp",)
    mlp_act: str = "swiglu"  # swiglu (3 mats) | gelu, relu2 (2 mats: up, down)

    # --- embeddings / io -------------------------------------------------------
    frontend: str = "none"  # none | patch (vlm) | frame (audio)
    n_frontend_tokens: int = 0  # patch/frame positions occupied per sample
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    # --- kernels ---------------------------------------------------------------
    # Route attention / SSD forward passes through the Pallas TPU kernels
    # (kernels/ops.py).  On a TPU they compile through Mosaic; elsewhere they
    # run in interpret mode (slow but exact).  Off by default: models take
    # the XLA path unless a caller turns it on, as chip_smoke.py and the
    # kernel tests do.
    use_pallas: bool = False

    def __post_init__(self):
        if self.n_kv_heads == 0:
            object.__setattr__(self, "n_kv_heads", self.n_heads)
        if self.head_dim == 0 and self.n_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if len(self.period) != len(self.mlp_pattern):
            raise ValueError("period and mlp_pattern must have equal length")
        if self.n_layers % len(self.period) != 0:
            raise ValueError(
                f"n_layers={self.n_layers} not divisible by period {len(self.period)}"
            )
        for kind in self.period:
            if kind not in ("attn", "ssm"):
                raise ValueError(f"unknown mixer kind {kind!r}")
        for kind in self.mlp_pattern:
            if kind not in ("mlp", "moe", "none"):
                raise ValueError(f"unknown mlp kind {kind!r}")
        if "moe" in self.mlp_pattern and (self.n_experts < 2 or self.top_k < 1):
            raise ValueError("moe layers need n_experts>=2 and top_k>=1")
        if self.router not in ("softmax", "sigmoid_bias"):
            raise ValueError(f"unknown router {self.router!r}")
        if self.pos_emb not in ("rope", "none"):
            raise ValueError(f"unknown pos_emb {self.pos_emb!r}")
        if "moe" in self.mlp_pattern and self.capacity_factor > 0:
            dropless = (self.router, self.routed_scale, self.shared_expert_ff, self.experts_held)
            if dropless != ("softmax", 1.0, 0, 0):
                raise ValueError(
                    "router, routed_scale, shared_expert_ff and experts_held are the dropless "
                    "layer's (capacity_factor=0)"
                )
        elif "moe" in self.mlp_pattern and (
            self.mlp_act != "relu2" or self.router != "sigmoid_bias"
        ):
            raise ValueError("the dropless layer: relu2 experts behind a sigmoid_bias router")

    # -- derived -----------------------------------------------------------
    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.period)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_inner(self) -> int:
        if self.ssm_n_heads:
            return self.ssm_n_heads * self.ssm_head_dim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def mlp_mats(self) -> int:
        """Weight matrices of one MLP or expert: swiglu 3, gelu and relu2 2."""
        return 3 if self.mlp_act == "swiglu" else 2

    @property
    def has_attention(self) -> bool:
        return "attn" in self.period

    @property
    def has_ssm(self) -> bool:
        return "ssm" in self.period

    @property
    def is_encoder(self) -> bool:
        return not self.causal

    @property
    def subquadratic(self) -> bool:
        """True if context cost/token is O(1) or O(window) — the long_500k
        eligibility rule: SSM, hybrid, or sliding-window attention."""
        if not self.has_attention:
            return True
        return self.has_ssm or self.window is not None

    def param_count(self) -> int:
        """Exact parameter count (embeddings included)."""
        total = self.vocab * self.d_model  # embed
        if not self.tie_embeddings:
            total += self.d_model * self.vocab  # head
        total += self.d_model  # final norm
        d, hd = self.d_model, self.head_dim
        for mixer, mlp in zip(self.period, self.mlp_pattern):
            n = self.n_periods
            total += n * d  # norm1
            if mixer == "attn":
                q = self.n_heads * hd
                kv = self.n_kv_heads * hd
                total += n * (d * q + 2 * d * kv + q * d)
                if self.qkv_bias:
                    total += n * (q + 2 * kv)
            else:
                di, g, N, H = self.d_inner, self.ssm_groups, self.ssm_state, self.ssm_heads
                conv_dim = di + 2 * g * N
                total += n * (
                    d * (2 * di + 2 * g * N + H)  # in_proj (x,z,B,C,dt)
                    + conv_dim * self.ssm_conv  # conv
                    + 3 * H  # A_log, D, dt_bias
                    + di  # gated norm
                    + di * d  # out_proj
                )
            if mlp != "none":
                total += n * d  # norm2
            n_mats = self.mlp_mats
            if mlp == "mlp":
                total += n * n_mats * d * self.d_ff
            elif mlp == "moe":
                total += n * (
                    d * self.n_experts  # router
                    + self.n_experts_held * n_mats * d * self.d_ff
                    + 2 * d * self.shared_expert_ff
                )
                if self.router == "sigmoid_bias":
                    total += n * self.n_experts  # selection bias
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k of n_experts, or of the
        held experts their share top_k * held / n_experts at balance)."""
        if "moe" not in self.mlp_pattern:
            return self.param_count()
        total = self.param_count()
        expert_mats = self.d_model * self.d_ff * self.mlp_mats
        held = self.n_experts_held
        active = self.top_k * held / self.n_experts
        for mlp in self.mlp_pattern:
            if mlp == "moe":
                total -= int(self.n_periods * (held - active) * expert_mats)
        return total


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell (paper-assigned shape sets)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def tokens_per_step(self) -> int:
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


def applicable_shapes(cfg: ArchConfig) -> Tuple[ShapeConfig, ...]:
    """The dry-run cells for an architecture, per the assignment rules:
    encoder-only archs skip decode shapes; long_500k requires sub-quadratic
    attention (SSM / hybrid / SWA)."""
    out = []
    for s in ALL_SHAPES:
        if s.kind == "decode" and cfg.is_encoder:
            continue
        if s.name == "long_500k" and not cfg.subquadratic:
            continue
        out.append(s)
    return tuple(out)
