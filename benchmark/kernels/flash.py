"""Operations and bytes one call of the flash-attention kernels needs
(tpukit/ops/pallas_attention.py), as functions of the shapes. Per chip: B
rows, H heads, S tokens (padded up to the kernel's 128-lane multiple is NOT
credited), head size D, bf16 operands.

Causal attention needs half of the S x S score matrix: forward is two matmuls
(q k^T, p v) of 2*S*S*D FLOPs each, halved = 2*S^2*D per head and row. The
backward pass needs four (dv, dp, dq, dk), 4*S^2*D; the recomputed scores are
not credited, as recompute never is. Bytes: every operand read once and every
result written once, the float32 log-sum-exp row included.
"""


def work(rec) -> dict:
    cfg = rec["cfg"]
    b, h, s, d = rec["rows_per_chip"], cfg.heads, rec["seq"], cfg.head_dim
    bh = b * h
    tensor = bh * s * d * 2  # one bf16 [B, H, S, D]
    row = bh * s * 4         # one float32 [B, H, S]
    fwd = (2.0 * s * s * d * bh, 4 * tensor + row)             # q k v -> o, lse
    bwd = (4.0 * s * s * d * bh, 8 * tensor + 2 * row)         # q k v o do -> dq dk dv
    dq = (2.0 * s * s * d * bh, 5 * tensor + 2 * row)          # split backward: dp, dq
    dkv = (2.0 * s * s * d * bh, 6 * tensor + 2 * row)         # dv, dk
    return {"flash_fwd": fwd, "flash_bwd": bwd, "flash_dq": dq, "flash_dkv": dkv}
