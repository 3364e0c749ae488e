"""proof_check_p95_ms: nearest-rank 95th percentile of the same checks."""

from benchmark.metrics._layers import p95_ms as read  # noqa: F401
