"""A validator checks the leader's COMMITTED quorum proof:
``harmony_tpu.consensus.fbft.Validator.on_committed`` (decode, quorum by
mask, host hash-to-G2, the scheduler's CONSENSUS lane, one fused
``agg_verify_b<bucket>`` program).  One proof per call, one caller in
turn; each round's objects (RoundConfig, Decider, Validator) are built
in set-up.  Fixtures and judge: ``gen``'s quorum proofs.
"""

from __future__ import annotations

from .. import gen, loops
from ._committee import roster

prepare = gen.Pending
judge = gen.judge


def programs(config: dict, mix: dict) -> list:
    return [f"agg_verify_b{config['committee_bucket']}"]


class Driver:
    items_per_call = 1

    def __init__(self, config: dict, mix: dict, fx):
        from harmony_tpu.consensus.fbft import RoundConfig, Validator
        from harmony_tpu.consensus.messages import FBFTMessage, MsgType
        from harmony_tpu.consensus.quorum import Decider, Policy
        from harmony_tpu.multibls import PrivateKeys

        vote = roster(config, fx)
        no_keys = PrivateKeys.from_keys([])
        self.rounds = []
        for it in fx.items:
            h = it.header
            cfg = RoundConfig(committee=list(fx.pubkeys),
                              block_num=h["block_num"], view_id=h["view_id"])
            v = Validator(no_keys, cfg,
                          Decider(Policy.STAKED, fx.pubkeys, vote))
            msg = FBFTMessage(msg_type=MsgType.COMMITTED,
                              view_id=h["view_id"], block_num=h["block_num"],
                              block_hash=it.block_hash,
                              sender_pubkeys=[fx.pubkeys[0]],
                              payload=it.proof)
            self.rounds.append((v, msg))

    def upload(self) -> None:
        from harmony_tpu import device as DV

        v = self.rounds[0][0]
        DV.get_committee_table(v.cfg.committee,
                               v.committee_points).device_array()

    def call(self, k: int) -> list:
        i = k % len(self.rounds)
        v, msg = self.rounds[i]
        return [(i, v.on_committed(msg))]

    def window(self, seconds: float, k: int) -> loops.Window:
        return loops.closed(self.call, self.items_per_call, seconds, k)
