"""Architecture registry: --arch <id> resolves here.

Each architecture is an ArchConfig at its published widths (``--full``;
the benchmark's configurations run these on the chip) plus a
smoke_config() reduction (same family/pattern, tiny dims, runnable on
CPU).
"""
from __future__ import annotations

from repro.models.api import ArchConfig


ARCHS: dict[str, ArchConfig] = {}


def _register(cfg: ArchConfig) -> ArchConfig:
    ARCHS[cfg.name] = cfg
    return cfg


# One module per assigned architecture (``--arch <id>`` resolves to the
# CONFIG defined there); the registry just aggregates them.
from . import (command_r_plus_104b, gemma2_27b, jamba_1_5_large_398b,  # noqa: E402
               llama4_maverick_400b_a17b, llama_3_2_vision_90b,
               mamba2_780m, qwen3_32b, qwen3_moe_235b_a22b, smollm_135m,
               whisper_tiny)

for _mod in (command_r_plus_104b, qwen3_32b, smollm_135m, gemma2_27b,
             llama_3_2_vision_90b, mamba2_780m, whisper_tiny,
             jamba_1_5_large_398b, qwen3_moe_235b_a22b,
             llama4_maverick_400b_a17b):
    _register(_mod.CONFIG)


# ---------------------------------------------------------------------------
# Smoke reductions: same family/pattern, tiny dims
# ---------------------------------------------------------------------------

def smoke_config(name: str) -> ArchConfig:
    cfg = ARCHS[name]
    period = cfg.period
    kw = dict(
        n_layers=2 * period, d_model=64,
        n_heads=4, n_kv_heads=max(1, 4 * cfg.n_kv_heads // cfg.n_heads),
        head_dim=16, d_ff=0 if cfg.d_ff == 0 else 96, vocab=211,
        frontend_len=8 if cfg.frontend_len else 0,
        window=8 if cfg.window else None,
        aux_dim=32, ce_chunk=64,
    )
    if cfg.n_experts:
        kw.update(n_experts=8, top_k=min(cfg.top_k, 2))
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=8)
    if cfg.n_decoder_layers:
        kw.update(n_decoder_layers=2)
    return cfg.scaled(**kw)


def get(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch '{name}'; known: {sorted(ARCHS)}")
    return ARCHS[name]
