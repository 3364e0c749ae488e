"""quorum_tally_ms.replay: the program's ``quorum_tally`` stage (the
stake-weighted quorum check), ms per replayed header."""

from benchmark.metrics._stages import reader

read = reader("quorum_tally")
