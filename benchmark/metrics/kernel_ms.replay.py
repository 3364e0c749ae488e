"""kernel_ms.replay: device time of agg_verify_batch_b256x64 per header,
from the trace."""

from benchmark.metrics._layers import kernel_ms as read  # noqa: F401
