"""decode_tick_device_ms, for the cells whose end-to-end metric carries the suffix .tput (see _shared.py)."""

from benchmark.layer_metrics._shared import decode_tick_device_ms as read  # noqa: F401
