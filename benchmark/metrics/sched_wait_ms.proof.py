"""sched_wait_ms.proof: scheduler wait per quorum-proof check."""

from benchmark.metrics._layers import sched_wait_ms as read  # noqa: F401
