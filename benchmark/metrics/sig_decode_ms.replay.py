"""sig_decode_ms.replay: the program's ``sig_decode`` stage (the 96-byte
aggregate signature's decode and checks), ms per replayed header."""

from benchmark.metrics._stages import reader

read = reader("sig_decode")
