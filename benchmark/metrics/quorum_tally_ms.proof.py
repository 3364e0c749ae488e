"""quorum_tally_ms.proof: the program's ``quorum_tally`` stage (the
stake-weighted quorum check), ms per quorum-proof check."""

from benchmark.metrics._stages import reader

read = reader("quorum_tally")
