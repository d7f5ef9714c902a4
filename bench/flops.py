"""Model FLOPs of one pod round, per token, from a configuration's shapes.

Counts the operations the forward and backward passes need (backward =
twice forward), for every token of a round: the device half and the aux
head of its group, then the server half and the tied LM head.  Causal
attention and the SSD's within-chunk term count the pairs a token
attends to (half the square); recomputation is not counted.
"""
from __future__ import annotations


def _llama_block(c: dict, seq: int) -> float:
    D, H, Hkv, hd, F = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"],
                        c["intermediate_size"])
    proj = 2 * (D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F)
    attn = 2 * 2 * H * hd * (seq / 2)      # QK^T and PV, causal half
    return proj + attn


def _mamba2_block(c: dict, seq: int) -> float:
    D, N, P, G = c["d_model"], c["d_state"], c["headdim"], c["ngroups"]
    Di = c["expand"] * D
    Hs = Di // P
    Q = min(c["chunk_size"], seq)
    conv_dim = Di + 2 * G * N
    proj = 2 * (D * (2 * Di + 2 * G * N + Hs) + Di * D)
    conv = 2 * c["d_conv"] * conv_dim
    ssd = (2 * N * (Q / 2) * G          # C B^T within a chunk, causal half
           + 2 * (Q / 2) * P * Hs       # scores times x
           + 2 * 2 * N * P * Hs)        # carried state: read and update
    return proj + conv + ssd


def _dims(c: dict):
    if c["family"] == "llama":
        return (c["num_hidden_layers"], c["hidden_size"], c["vocab_size"],
                _llama_block)
    if c["family"] == "mamba2":
        return c["n_layer"], c["d_model"], c["vocab_size"], _mamba2_block
    raise ValueError(f"no FLOP count for family {c['family']!r}")


def flops_per_token(config: dict, seq_len: int) -> float:
    L, D, V, block = _dims(config)
    b = block(config, seq_len)
    aux = b + 2 * D * config["aux_dim"] + 2 * config["aux_dim"] * V
    forward = L * b + 2 * D * V + aux
    return 3.0 * forward
