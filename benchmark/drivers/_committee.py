"""The committee as the program takes it: keys and a stake roster."""

from __future__ import annotations


def roster(config: dict, fx):
    """harmony_tpu's voting-power roster of the fixtures' committee."""
    from harmony_tpu.consensus.votepower import Slot, compute_roster
    from harmony_tpu.numeric import Dec, new_dec, one_dec

    share = Dec.from_str(config["harmony_vote_share"])
    slots = [Slot(f"slot{i}", pk, None if s is None else new_dec(s))
             for i, (pk, s) in enumerate(zip(fx.pubkeys, fx.stakes))]
    return compute_roster(slots, share, one_dec().sub(share))
