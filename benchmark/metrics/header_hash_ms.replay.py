"""header_hash_ms.replay: the program's ``header_hash`` stage (Keccak of
the header's RLP), ms per replayed header."""

from benchmark.metrics._stages import reader

read = reader("header_hash")
