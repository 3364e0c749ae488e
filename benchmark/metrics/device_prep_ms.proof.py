"""device_prep_ms.proof: the program's ``device_prep`` stage (host prep
of the device program's inputs), ms per quorum-proof check."""

from benchmark.metrics._stages import reader

read = reader("device_prep")
