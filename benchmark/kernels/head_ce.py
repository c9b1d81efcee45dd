"""Operations and bytes one call of the fused head + cross-entropy kernels
needs (tpukit/ops/fused_head_ce.py). Per chip: T = rows x tokens hidden rows
of width `dim` (bf16) against the float32 head [dim, padded vocab].

Forward: one matmul, 2*T*dim*V. Backward: two (dh = dlogits W^T, dW = h^T
dlogits), 4*T*dim*V; the logits recomputed in the backward pass are not
credited. Bytes: h and W read, the per-row losses written; backward reads h,
W and the per-row statistics and writes dh (bf16) and dW (float32).
"""


def work(rec) -> dict:
    cfg = rec["cfg"]
    t, dim, v = rec["rows_per_chip"] * rec["seq"], cfg.dim, cfg.padded_vocab_size
    h, w = t * dim * 2, dim * v * 4
    return {
        "head_ce_fwd": (2.0 * t * dim * v, h + w + 3 * t * 4),
        "head_ce_bwd": (4.0 * t * dim * v, 2 * h + 2 * w + 3 * t * 4),
    }
