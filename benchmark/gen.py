"""The one traffic generator: committee and proof pool from ``--seed``.

Everything here is the benchmark's own (the keys, stakes, headers and
signatures are made by ``reference/``, never by the program), so the
reference judges the program on inputs the program did not make.

A cell's pool is ``traffic["pool"]`` distinct block headers of the
config's shard and era, each with a commit proof (aggregate signature
|| bitmap) by the committee.  Every seed gives the same sizes: the
same committee width, exactly ``slots // absent_every`` slots absent
from each valid bitmap, exactly ``pool // invalid_every`` invalid
proofs of the kinds in ``INVALID`` in turn; only which slots, which
proofs and the bytes differ.

The bigint curve work (a G1 multiply per key, a hash-to-G2 and a G2
multiply per proof, and the reference check of every proof after the
window) runs in a pool of spawned worker processes, so it overlaps the
program's warm-up and never touches JAX.
"""

from __future__ import annotations

import multiprocessing
import random
import statistics
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, field
from fractions import Fraction

from .reference import check as C
from .reference.curve import G1_GEN, g1, g2
from .reference.hash_to_curve import hash_to_g2
from .reference.params import R_ORDER
from .reference.serialize import g1_compress, g2_compress

# A bitmap must clear 2/3 of the vote by this much, so no rounding of
# the program's fixed-point tally can decide it (the margin is the
# traffic's, the rule is the reference's).
QUORUM_MARGIN = Fraction(1, 50)
# The invalid proofs, in turn: a signature not by the bitmap's signers;
# a bitmap with one signer's bit cleared (still a quorum); a valid
# signature by signers short of 2/3 of the stake (the lowest-powered
# slots: on mainnet V3_3 more than 2/3 of the slots, so a tally of
# slots instead of stake accepts it).
INVALID = ("forged_signature", "bitmap_mismatch", "short_of_quorum")
BLOCKS_PER_EPOCH = 32768  # mainnet, 2 s blocks (shard/committee schedule)


def rng_for(seed: int, *tags: str) -> random.Random:
    return random.Random("/".join(("harmony-bench", str(seed)) + tags))


def spawn_pool(n: int, initializer=None, initargs=()) -> ProcessPoolExecutor:
    return ProcessPoolExecutor(
        max_workers=n, mp_context=multiprocessing.get_context("spawn"),
        initializer=initializer, initargs=initargs)


def bitmap_bytes(bits: list) -> bytes:
    out = bytearray((len(bits) + 7) // 8)
    for i, b in enumerate(bits):
        if b:
            out[i >> 3] |= 1 << (i & 7)
    return bytes(out)


@dataclass
class Item:
    """One block and its commit proof."""

    header: dict          # v3 header fields (reference/check.py order)
    signers: list         # 0/1 per slot: whose keys sign
    invalid: str          # "" (valid) or one of INVALID
    bits: list = field(default_factory=list)  # as the bitmap carries them
    block_hash: bytes = b""
    proof: bytes = b""    # sig (96 B) || bitmap

    @property
    def payload(self) -> bytes:
        h = self.header
        return C.commit_payload(self.block_hash, h["block_num"], h["view_id"])


@dataclass
class Fixtures:
    slots: int
    stakes: list          # effective stake per slot, None = Harmony-operated
    harmony_share: Fraction
    pubkeys: list = field(default_factory=list)   # compressed, 48 B
    points: list = field(default_factory=list)    # affine G1 tuples
    items: list = field(default_factory=list)

    @property
    def power(self) -> list:
        return C.voting_power(self.stakes, self.harmony_share)


def _stakes(rng: random.Random, config: dict) -> list:
    """Harmony slots first, then external operators of 1..cap keys
    (HIP-16 caps one operator at ``slots_limit`` of the slots), each
    key's effective stake clamped to 0.85-1.15 of the median as EPoS
    does, in whole ONE."""
    n, n_hmy = config["slots"], config["harmony_slots"]
    cap = max(1, int(config["slots_limit"] * n))
    operators, left = [], n - n_hmy
    while left:
        operators.append(min(rng.randint(1, cap), left))
        left -= operators[-1]
    raw = [rng.lognormvariate(0.0, 0.6) for _ in operators]
    med = statistics.median(raw)
    per_key = [round(1_000_000 * min(max(r, 0.85 * med), 1.15 * med) / med)
               for r in raw]
    stakes = [None] * n_hmy
    for k, s in zip(operators, per_key):
        stakes += [s] * k
    return stakes


def _quorum_bits(rng, power: list, absent: int) -> list:
    bar = Fraction(2, 3) + QUORUM_MARGIN
    while True:
        bits = [1] * len(power)
        for i in rng.sample(range(len(power)), absent):
            bits[i] = 0
        if sum(p for p, b in zip(power, bits) if b) > bar:
            return bits


def _short_bits(rng, power: list) -> list:
    """The lowest-powered slots (ties in seeded order) while they hold
    less than 2/3 of the vote by the margin."""
    order = sorted(range(len(power)), key=lambda i: (power[i], rng.random()))
    bits, total = [0] * len(power), Fraction(0)
    for i in order:
        if total + power[i] >= Fraction(2, 3) - QUORUM_MARGIN:
            break
        bits[i], total = 1, total + power[i]
    return bits


def plan(config: dict, traffic: dict, seed: int) -> tuple:
    """The cheap, host-only part: secret keys, stakes, bitmaps, headers
    and which proofs are invalid.  Returns (fixtures without keys or
    signatures, secret keys)."""
    rng = rng_for(seed, config["name"], traffic["name"])
    n = config["slots"]
    sks = [rng.randrange(1, R_ORDER) for _ in range(n)]
    fx = Fixtures(slots=n, stakes=_stakes(rng, config),
                  harmony_share=Fraction(config["harmony_vote_share"]))
    power = fx.power
    pool = traffic["pool"]
    bad = sorted(rng.sample(range(pool), pool // traffic["invalid_every"]))
    kinds = dict(zip(bad, INVALID * pool))
    first = (config["epoch"] * BLOCKS_PER_EPOCH
             + rng.randrange(BLOCKS_PER_EPOCH - pool))
    view = first + rng.randrange(1, 1000)
    stamp = 1_600_000_000 + 2 * first
    for j in range(pool):
        kind = kinds.get(j, "")
        bits = (_short_bits(rng, power) if kind == "short_of_quorum" else
                _quorum_bits(rng, power, n // traffic["absent_every"]))
        header = {
            "parent_hash": rng.randbytes(32), "root": rng.randbytes(32),
            "tx_root": rng.randbytes(32), "receipt_root": rng.randbytes(32),
            "out_cx_root": rng.randbytes(32), "block_num": first + j,
            "timestamp": stamp + 2 * j, "extra": b"", "view_id": view + j,
            "epoch": config["epoch"], "shard_id": config["shard"],
            "last_commit_sig": rng.randbytes(96),
            "last_commit_bitmap": bitmap_bytes(
                _quorum_bits(rng, power, n // traffic["absent_every"])),
            "shard_state": b"", "vrf": rng.randbytes(32), "vdf": b"",
            "cross_links": b"", "slashes": b"",
        }
        it = Item(header=header, signers=bits, invalid=kind,
                  bits=list(bits))
        if it.invalid == "bitmap_mismatch":
            # one signer's bit cleared: still a quorum, no longer the
            # signers of the signature
            it.bits[max(i for i, b in enumerate(bits) if b)] = 0
        fx.items.append(it)
    return fx, sks


# -- worker tasks (run in spawned processes) ----------------------------------

def _pubkeys(sks: list) -> list:
    out = []
    for sk in sks:
        pt = g1.mul(G1_GEN, sk)
        out.append((g1_compress(pt), pt))
    return out


def _seal(header: dict, agg_sk: int) -> tuple:
    """(block hash, compressed aggregate signature over its commit
    payload): one hash-to-G2 and one G2 multiply by the signers' summed
    secret keys, which equals the sum of their signatures."""
    block_hash = C.header_hash(header)
    payload = C.commit_payload(block_hash, header["block_num"],
                               header["view_id"])
    return block_hash, g2_compress(g2.mul(hash_to_g2(payload), agg_sk))


class Pending:
    """Fixtures being made in a worker pool; ``result()`` waits."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 n_workers: int):
        self.fx, sks = plan(config, traffic, seed)
        self._pool = spawn_pool(n_workers)
        step = max(1, len(sks) // n_workers)
        self._keys = [self._pool.submit(_pubkeys, sks[i:i + step])
                      for i in range(0, len(sks), step)]
        self._seals = []
        for it in self.fx.items:
            agg = sum(sk for sk, b in zip(sks, it.signers) if b) % R_ORDER
            if it.invalid == "forged_signature":
                agg = (agg + 1) % R_ORDER
            self._seals.append(self._pool.submit(_seal, it.header, agg))

    def close(self) -> None:
        """Stop the workers (at once if the fixtures were never taken)."""
        self._pool.shutdown(wait=True, cancel_futures=True)

    def result(self) -> Fixtures:
        try:
            for fut in self._keys:
                for pk, pt in fut.result():
                    self.fx.pubkeys.append(pk)
                    self.fx.points.append(pt)
            for it, fut in zip(self.fx.items, self._seals):
                it.block_hash, sig = fut.result()
                it.proof = sig + bitmap_bytes(it.bits)
        finally:
            self.close()
        return self.fx


# -- the reference over the pool ----------------------------------------------

_REF: dict = {}


def _ref_init(points: list, power: list) -> None:
    _REF["points"], _REF["power"] = points, power


def _ref_one(payload: bytes, proof: bytes) -> bool:
    return C.check_proof(_REF["points"], _REF["power"], payload, proof)


def _control_one(payload: bytes, proof: bytes) -> bool:
    """The control: the reference with its pairing check left out, a
    validator that trusts a well-formed proof whose bitmap reaches
    quorum.  It breaks the configuration's guarantee (every decision
    equals the reference's), so it has to come out not correct."""
    return C.check_proof(_REF["points"], _REF["power"], payload, proof,
                         pairing=False)


def reference(fx: Fixtures, indices, n_workers: int,
              control: bool = False) -> dict:
    """{pool index: the reference's decision} (or the control's)."""
    fn = _control_one if control else _ref_one
    indices = sorted(set(indices))
    with spawn_pool(n_workers, _ref_init, (fx.points, fx.power)) as pool:
        futs = [pool.submit(fn, fx.items[i].payload, fx.items[i].proof)
                for i in indices]
        return {i: f.result() for i, f in zip(indices, futs)}


def judge(fx: Fixtures, decisions: list, n_workers: int) -> dict:
    """Every (pool index, decision) against the reference's answer for
    that proof, and the reference against how the proof was made (an
    invalid one must be refused, every other accepted).  Each count
    has the limit 0."""
    ref = reference(fx, [i for i, _ in decisions], n_workers)
    return {
        "mismatch": sum(ref[i] != bool(d) for i, d in decisions),
        "reference_vs_made": sum(ok == bool(fx.items[i].invalid)
                                 for i, ok in ref.items()),
    }
