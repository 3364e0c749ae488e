"""mask_ms.replay: the program's ``mask`` stage (bitmap to signer
vector), ms per replayed header."""

from benchmark.metrics._stages import reader

read = reader("mask")
