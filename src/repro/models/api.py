"""Unified architecture description consumed by the model zoo.

One :class:`ArchConfig` describes any of the assigned architectures.  The
layer stack is a repeating *period*: ``pattern`` lists (mixer, ffn) pairs;
the stack is ``pattern * n_periods`` where ``n_periods = n_layers /
len(pattern)``.  Per-position parameters are stacked over periods so the
forward pass scans over periods (HLO size independent of depth).

Mixers:  "attn" (global self-attn), "local" (sliding window), "mamba",
         "cross" (cross-attention to frontend embeddings), "none"
FFNs:    "dense", "moe", "none"
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

from .attention import AttentionConfig
from .mamba import MambaConfig
from .mlp import MlpConfig, MoeConfig


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                       # lm | vlm | ssm | hybrid | moe | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    pattern: tuple = (("attn", "dense"),)
    head_dim: int | None = None
    qk_norm: bool = False
    attn_softcap: float | None = None
    final_softcap: float | None = None
    window: int | None = None          # sliding-window size for "local" mixers
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    activation: str = "swiglu"
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.0
    # Mamba / SSD
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    # enc-dec (audio): n_layers counts encoder layers; decoder mirrors it
    n_decoder_layers: int = 0
    # vlm / audio frontend stub: number of frontend embedding positions
    # (supplied pre-computed by input_specs); 0 = not used
    frontend_len: int = 0
    # FedOptima aux head bottleneck dim (factorized aux classifier)
    aux_dim: int = 512
    # loss chunking (sequence positions per CE chunk)
    ce_chunk: int = 512
    # query-chunk size for the jnp attention path (memory bound)
    attn_chunk: int = 1024

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def n_periods(self) -> int:
        assert self.n_layers % self.period == 0, (self.name, self.n_layers, self.period)
        return self.n_layers // self.period

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    def attn_cfg(self, mixer: str) -> AttentionConfig:
        return AttentionConfig(
            d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, qk_norm=self.qk_norm,
            attn_softcap=self.attn_softcap,
            window=self.window if mixer == "local" else None,
            rope_theta=self.rope_theta, causal=(self.family != "audio_enc"),
            chunk_q=self.attn_chunk)

    def cross_cfg(self) -> AttentionConfig:
        return AttentionConfig(
            d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, causal=False, chunk_q=self.attn_chunk)

    def mlp_cfg(self) -> MlpConfig:
        return MlpConfig(d_model=self.d_model, d_ff=self.d_ff, activation=self.activation)

    def moe_cfg(self) -> MoeConfig:
        return MoeConfig(d_model=self.d_model, d_ff=self.d_ff,
                         n_experts=self.n_experts, top_k=self.top_k,
                         activation=self.activation)

    def mamba_cfg(self) -> MambaConfig:
        return MambaConfig(d_model=self.d_model, d_state=self.ssm_state,
                           head_dim=self.ssm_head_dim, chunk=self.ssm_chunk)

    def scaled(self, **kw) -> "ArchConfig":
        """Reduced copy for smoke tests."""
        return replace(self, **kw)
