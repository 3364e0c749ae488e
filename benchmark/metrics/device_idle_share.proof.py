"""device_idle_share.proof: device idle share of the traced slice of
quorum-proof checks, in percent."""

from benchmark.metrics._layers import device_idle_pct as read  # noqa: F401
