"""Kernels: the fused head + cross-entropy kernels' share of their roofline
over the traced window (benchmark/kernels/head_ce.py)."""

from benchmark import common
from benchmark.kernels import roofline


def read(rec):
    got = roofline.share(rec, common.load_by_name("kernels", "head_ce", rec["root"]))
    return got[0] if got else None
