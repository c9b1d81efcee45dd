"""Model FLOPs per token of the gpt2-* block, copied from
tpukit/obs/meter.py (PaLM-appendix convention) so that the yardstick is the
benchmark's own: forward = 2 per matmul parameter + 4 * S * inner_dim per
layer for the score and value matmuls (not halved for causality); training =
3 x forward; recompute is never credited. Embedding gathers are excluded, the
untied lm_head is counted at its padded width (the FLOPs actually run).
tests/benchmark_tests checks it against the original for both configurations.
"""

from __future__ import annotations


def matmul_param_count(dim: int, heads: int, head_dim: int, layers: int,
                       padded_vocab: int, ffn_mult: int = 4) -> int:
    inner = heads * head_dim
    per_layer = 3 * dim * inner + inner * dim + 2 * dim * (dim * ffn_mult)
    return layers * per_layer + dim * padded_vocab


def train_flops_per_token(dim: int, heads: int, head_dim: int, layers: int,
                          padded_vocab: int, seq_len: int, ffn_mult: int = 4) -> float:
    attn = 4 * seq_len * heads * head_dim * layers
    return 3.0 * (2 * matmul_param_count(dim, heads, head_dim, layers, padded_vocab, ffn_mult) + attn)


def cfg_train_flops_per_token(cfg, seq_len: int) -> float:
    """The same, from a GPTConfig-like object."""
    return train_flops_per_token(cfg.dim, cfg.heads, cfg.head_dim, cfg.num_layers,
                                 cfg.padded_vocab_size, seq_len, cfg.ffn_mult)
