"""Model FLOPs per token, pinned by hand counts."""
import json
from pathlib import Path

import pytest

from bench.flops import flops_per_token

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_smollm_135m_at_2048_by_hand():
    D, F, V, A, S = 576, 1536, 49152, 512, 2048
    q_o = 2 * (576 * 576)                 # wq and wo: 9 heads of 64
    k_v = 2 * (576 * 192)                 # wk and wv: 3 kv heads of 64
    mlp = 3 * 576 * 1536                  # gate, up, down
    layer = 2 * (q_o + k_v + mlp) + 2 * 2 * 576 * (S / 2)   # + causal attn
    head = 2 * D * V                      # tied LM head
    aux = layer + 2 * D * A + 2 * A * V   # aux block and factorized head
    by_hand = 3 * (30 * layer + head + aux)
    got = flops_per_token(_config("smollm-135m"), S)
    assert got == pytest.approx(by_hand, rel=1e-12)
    assert got == pytest.approx(1.17e9, rel=0.05)     # about 1.2 GFLOP


def test_mamba2_780m_at_1024_by_hand():
    D, Di, H, P, N, Q, V, A = 1536, 3072, 48, 64, 128, 256, 50280, 512
    proj = 2 * (D * (2 * Di + 2 * N + H) + Di * D)
    conv = 2 * 4 * (Di + 2 * N)
    ssd = 2 * N * Q / 2 + 2 * Q / 2 * P * H + 4 * N * P * H
    layer = proj + conv + ssd
    by_hand = 3 * (48 * layer + 2 * D * V + layer + 2 * D * A + 2 * A * V)
    got = flops_per_token(_config("mamba2-780m"), 1024)
    assert got == pytest.approx(by_hand, rel=1e-12)
    assert got == pytest.approx(5.3e9, rel=0.05)


def test_unknown_family_is_an_error():
    with pytest.raises(ValueError):
        flops_per_token({"family": "rwkv"}, 128)
