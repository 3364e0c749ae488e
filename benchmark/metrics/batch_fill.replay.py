"""batch_fill.replay: live headers over padded slots of the replay
dispatches, in percent."""

from benchmark.metrics._layers import batch_fill_pct as read  # noqa: F401
