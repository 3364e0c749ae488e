"""device_prep_ms.replay: the program's ``device_prep`` stage (host prep
of the device program's inputs), ms per replayed header."""

from benchmark.metrics._stages import reader

read = reader("device_prep")
