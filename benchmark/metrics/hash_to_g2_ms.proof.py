"""hash_to_g2_ms.proof: host hash-to-G2 per quorum-proof check."""

from benchmark.metrics._layers import hash_to_g2_ms as read  # noqa: F401
