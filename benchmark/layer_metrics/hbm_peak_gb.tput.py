"""hbm_peak_gb, for the cells whose end-to-end metric carries the suffix .tput (see _shared.py)."""

from benchmark.layer_metrics._shared import hbm_peak_gb as read  # noqa: F401
