"""kernel_ms.proof: device time of agg_verify_b256 per check, from the
trace."""

from benchmark.metrics._layers import kernel_ms as read  # noqa: F401
