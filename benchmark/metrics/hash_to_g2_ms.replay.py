"""hash_to_g2_ms.replay: host hash-to-G2 per replayed header."""

from benchmark.metrics._layers import hash_to_g2_ms as read  # noqa: F401
