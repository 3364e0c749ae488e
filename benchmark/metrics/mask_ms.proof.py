"""mask_ms.proof: the program's ``mask`` stage (bitmap to signer
vector), ms per quorum-proof check."""

from benchmark.metrics._stages import reader

read = reader("mask")
