"""Kernels: the flash-attention kernels' share of their roofline over the
traced window (benchmark/kernels/flash.py gives the FLOPs and bytes)."""

from benchmark import common
from benchmark.kernels import roofline


def read(rec):
    got = roofline.share(rec, common.load_by_name("kernels", "flash", rec["root"]))
    return got[0] if got else None
