"""Catch-up replay of staking-era headers:
``harmony_tpu.chain.engine.Engine.verify_headers_batch`` over one
staged-sync window per call, one caller in turn (per header: sig
decode, quorum by mask, host hash-to-G2; then the window on the
scheduler's SYNC lane as one fused ``agg_verify_batch_b<bucket>x<batch>``
program).  A fresh Engine per pass of the pool, so its verified-seal
cache never answers: real replay sees each header once.  Fixtures and
judge: ``gen``'s quorum proofs.
"""

from __future__ import annotations

from .. import gen, loops
from ._committee import roster

prepare = gen.Pending
judge = gen.judge


def programs(config: dict, mix: dict) -> list:
    return [f"agg_verify_batch_b{config['committee_bucket']}"
            f"x{mix['batch']}"]


class Driver:
    def __init__(self, config: dict, mix: dict, fx):
        from harmony_tpu.chain.engine import EpochContext
        from harmony_tpu.chain.header import Header
        from harmony_tpu.consensus.quorum import Policy

        self.items_per_call = mix["batch"]
        if len(fx.items) % self.items_per_call:
            raise ValueError("the pool must hold whole replay windows")
        self.ctx = EpochContext(list(fx.pubkeys), Policy.STAKED,
                                roster(config, fx))
        self.windows = []
        for start in range(0, len(fx.items), self.items_per_call):
            batch = list(range(start, start + self.items_per_call))
            self.windows.append((batch, [
                (Header(version="v3", **fx.items[i].header),
                 fx.items[i].proof[:96], fx.items[i].proof[96:])
                for i in batch]))
        self.engine = None

    def upload(self) -> None:
        self.ctx.committee_table().device_array()

    def call(self, k: int) -> list:
        from harmony_tpu.chain.engine import Engine

        w = k % len(self.windows)
        if w == 0:
            self.engine = Engine(lambda shard, epoch: self.ctx, device=True)
        batch, items = self.windows[w]
        return list(zip(batch, self.engine.verify_headers_batch(items)))

    def window(self, seconds: float, k: int) -> loops.Window:
        return loops.closed(self.call, self.items_per_call, seconds, k)
