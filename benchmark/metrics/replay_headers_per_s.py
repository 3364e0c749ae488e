"""replay_headers_per_s: headers decided over the whole window, from its
start to the end of its last window of headers."""

from benchmark.metrics._layers import items_per_s as read  # noqa: F401
