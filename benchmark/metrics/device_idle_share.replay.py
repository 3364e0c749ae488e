"""device_idle_share.replay: device idle share of the traced slice of
replay windows, in percent."""

from benchmark.metrics._layers import device_idle_pct as read  # noqa: F401
