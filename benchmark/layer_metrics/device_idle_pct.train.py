"""device_idle_pct, for the cells whose end-to-end metric carries the suffix .train (see _shared.py)."""

from benchmark.layer_metrics._shared import device_idle_pct as read  # noqa: F401
