"""proof_check_p50_ms: median time of one quorum-proof check, message in
to bool out, over every check of the window."""

from benchmark.metrics._layers import p50_ms as read  # noqa: F401
