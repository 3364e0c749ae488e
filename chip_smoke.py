#!/usr/bin/env python3
"""Chip smoke: harmony-tpu's FBFT quorum-check path, once, on one TPU.

Run it from the root of a checkout, on the chip (through the chip tool):

    python chip_smoke.py [--seed N]

One process, and it alone touches JAX.  The phases run in order through
the node's own entry points (node -> sched -> device -> ops), with the
real kernels, and the first failure ends the run:

  device    the first JAX device must be a TPU;
  warmup    ``aot.warmup`` over the manifest programs the phases below
            dispatch (what ``cli.py`` runs at node start);
  quorum    masked aggregate verifies against the mainnet V5 shard
            committee (200 slots, bucket 256) on the sched CONSENSUS
            lane, committee table resident on the device: valid quorums
            with about a sixth of the signers dropped, one forged
            signature, one bitmap the signature does not match;
  replay    one fused batch of 64 headers with distinct payloads
            against a 250-key committee on the sched SYNC lane, one
            header invalid;
  single    8 independent single-signature verifies, one forged;
  localnet  an in-process 4-node localnet, 4 x 50 keys on a 200-key
            committee (multi-key validators), commits blocks.

Every decision is compared with the host reference (``ref/``, native
where it loads).  After every phase nothing may have taken a host path:
no reference fallback, breaker closed, no scheduler shed, no AOT
fallback, no program compiled after warmup, and the phase's device
counters moved.

Times printed are smoke timings of one run, set-up included — not
benchmark numbers.  The last line of stdout is the JSON result; it is
printed only when every phase passed.  Without a TPU, or without the
rest of the repository beside it, the script exits non-zero and prints
no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

QUORUM_KEYS = 200      # mainnet V5 shard slots (config/sharding.py)
QUORUM_VALID = 6       # valid quorums, plus one forged, one mismatched
REPLAY_KEYS = 250      # the historic 4 x 250 mainnet shard committee
REPLAY_WIDTH = 64      # one pinned replay batch bucket
REPLAY_WINDOW_S = 2.0  # SYNC-lane flush window: all 64 headers in one
SINGLE_WIDTH = 8       # the pinned single-verify bucket
LOCALNET_NODES = 4
LOCALNET_KEYS_PER_NODE = 50
LOCALNET_BLOCKS = 3
LOCALNET_TIMEOUT_S = 900.0

# Every program the phases dispatch.  The localnet reuses them: its
# committee is bucket 256 and its scheduler dispatches one request at a
# time (max_batch=1), so votes run verify_w8 and proofs and seals run
# agg_verify_b256.  Each whole pairing program costs minutes to compile
# cold, so nothing else is warmed.
PROGRAMS = ("agg_verify_b256", "agg_verify_batch_b256x64", "verify_w8")


class SmokeFailure(Exception):
    """A phase's check failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class Guard:
    """The no-hidden-host-path invariants, checked after every phase
    (as differences from when the guard was made)."""

    def __init__(self):
        from harmony_tpu import aot
        from harmony_tpu import device as DV
        from harmony_tpu.sched.scheduler import SHED

        self.DV = DV
        self.host_paths = {
            "reference fallbacks": lambda: DV.COUNTERS["ref_fallback"],
            "scheduler sheds onto the host reference": SHED.total,
            "AOT fallbacks": aot.FALLBACKS.total,
        }
        self.base = {k: f() for k, f in self.host_paths.items()}
        self.jit_miss0 = None
        self.before = {}

    def start(self) -> None:
        self.before = {k: self.DV.COUNTERS[k] for k in self.DV.COUNTERS}

    def warmed(self) -> None:
        """From here on, a JIT first use is a serving-path compile."""
        self.jit_miss0 = self.DV.JIT["miss"]

    def after(self, phase: str, moved=()) -> None:
        DV = self.DV
        for what, f in self.host_paths.items():
            n = f() - self.base[what]
            check(n == 0, f"{phase}: {n:g} {what}")
        check(DV.BREAKER.state == "closed",
              f"{phase}: device breaker is {DV.BREAKER.state}")
        if self.jit_miss0 is not None:
            check(DV.JIT["miss"] == self.jit_miss0,
                  f"{phase}: {DV.JIT['miss'] - self.jit_miss0} programs "
                  "compiled after warmup (not in the warmed set)")
        for k in moved:
            check(DV.COUNTERS[k] > self.before[k],
                  f"{phase}: device counter {k!r} did not move")


def _keys(seed: int, tag: str, n: int) -> list:
    from harmony_tpu import bls as B

    return [B.PrivateKey.generate(b"chip-smoke/%d/%s/%d"
                                  % (seed, tag.encode(), i))
            for i in range(n)]


def _g2mul():
    from harmony_tpu.ref import native as NB
    from harmony_tpu.ref.curve import g2

    return NB.g2_mul if NB.available() else g2.mul


def _aggregate_sig(h, keys, bits):
    """The aggregate of every signer's signature over ``h``: the sum of
    their scalars times h (one G2 multiply instead of one per key)."""
    from harmony_tpu.ref.curve import R_ORDER

    sk = sum(k.scalar for k, b in zip(keys, bits) if b) % R_ORDER
    return _g2mul()(h, sk)


def _ref_agg(points, bits, h, sig) -> bool:
    from harmony_tpu.ref import bls as RB

    signers = [p for p, b in zip(points, bits) if b]
    return RB.verify_hashed(RB.aggregate_pubkeys(signers), h, sig)


def _quorum_bits(rng, n: int):
    import numpy as np

    bits = np.ones(n, dtype=np.int32)
    bits[rng.choice(n, size=n // 6, replace=False)] = 0
    return bits


def phase_device() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r} "
          f"({dev.device_kind}); this smoke runs only on the chip")
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def phase_warmup(guard: Guard, programs=PROGRAMS) -> dict:
    from harmony_tpu import aot

    manifest = aot.load_manifest()
    check(manifest is not None, "no committed compile manifest")
    missing = set(programs) - set(aot.manifest_names(manifest))
    check(not missing, f"programs not in the compile manifest: {missing}")
    filtered = {"programs": [
        dict(fam, names=[n for n in fam["names"] if n in programs])
        for fam in manifest["programs"]]}
    t0 = time.monotonic()
    stats = aot.warmup(filtered)
    wall = time.monotonic() - t0
    for name, rec in sorted(stats["per_program"].items()):
        cache = {"cached": "hit", "compiled": "miss"}.get(
            rec["outcome"], rec["outcome"])
        say(f"warmup {name}: cache {cache}, lower "
            f"{rec['lower_s']:.1f} s, compile {rec['compile_s']:.1f} s")
    say(f"warmup mode={stats['mode']} programs={stats['programs']} "
        f"compiled={stats['compiled']} cached={stats['cached']} "
        f"failed={stats['failed']} compile_s={stats['compile_s']:.1f} "
        f"wall_s={wall:.1f} cache_dir={aot.cache_dir()}")
    check(stats["failed"] == 0 and stats["warmed"] == len(programs),
          f"warmup warmed {stats['warmed']} of {len(programs)} programs")
    guard.warmed()
    guard.after("warmup")
    return stats


def phase_quorum(seed: int, guard: Guard, n_keys: int = QUORUM_KEYS,
                 n_valid: int = QUORUM_VALID) -> int:
    import numpy as np

    from harmony_tpu import device as DV
    from harmony_tpu import sched
    from harmony_tpu.ref.hash_to_curve import hash_to_g2

    guard.start()
    rng = np.random.default_rng(seed)
    keys = _keys(seed, "quorum", n_keys)
    points = [k.pub.point for k in keys]
    table = DV.get_committee_table([k.pub.bytes for k in keys], points)
    cases = []  # (label, bits, payload, h, sig, expected)
    for _ in range(n_valid):
        bits = _quorum_bits(rng, n_keys)
        payload = rng.bytes(32)
        h = hash_to_g2(payload)
        cases.append(("valid", bits, payload, h,
                      _aggregate_sig(h, keys, bits), True))
    bits = _quorum_bits(rng, n_keys)
    payload = rng.bytes(32)
    h = hash_to_g2(payload)
    forged = _g2mul()(_aggregate_sig(h, keys, bits), 2)
    cases.append(("forged signature", bits, payload, h, forged, False))
    label, bits, payload, h, sig, _ = cases[0]
    short = bits.copy()
    short[int(np.flatnonzero(short)[0])] = 0  # still a quorum
    cases.append(("bitmap not matching signature", short, payload, h,
                  sig, False))
    sched.reset()
    sched.configure(enabled=True)
    lat = []
    for label, bits, payload, h, sig, expected in cases:
        t0 = time.monotonic()
        got = sched.agg_verify(table, bits, payload, sig,
                               lane=sched.Lane.CONSENSUS)
        lat.append(time.monotonic() - t0)
        ref = _ref_agg(points, bits, h, sig)
        check(got == ref, f"quorum {label}: device {got} != ref {ref}")
        check(got == expected, f"quorum {label}: decided {got}")
    sched.reset()
    say(f"quorum {n_keys} keys (bucket {table.size}): {len(cases)} "
        f"checks match the reference ({n_valid} accepted, 2 rejected); "
        f"per-check s {[round(x, 4) for x in lat]}")
    guard.after("quorum", moved=("agg_verify",))
    return len(cases)


def phase_replay(seed: int, guard: Guard, n_keys: int = REPLAY_KEYS,
                 width: int = REPLAY_WIDTH) -> int:
    import numpy as np

    from harmony_tpu import device as DV
    from harmony_tpu import sched
    from harmony_tpu.ref.hash_to_curve import hash_to_g2
    from harmony_tpu.sched.scheduler import FLUSHES

    guard.start()
    rng = np.random.default_rng(seed + 1)
    keys = _keys(seed, "replay", n_keys)
    points = [k.pub.point for k in keys]
    table = DV.CommitteeTable(points)
    bits_list, hs, sigs = [], [], []
    payloads = set()
    for _ in range(width):
        payload = rng.bytes(32)
        payloads.add(payload)
        bits = _quorum_bits(rng, n_keys)
        h = hash_to_g2(payload)
        bits_list.append(bits)
        hs.append(h)
        sigs.append(_aggregate_sig(h, keys, bits))
    check(len(payloads) == width, "replay payloads not distinct")
    bad = width // 3
    sigs[bad] = sigs[bad + 1]  # a real seal, of another header
    sched.reset()
    sched.configure(enabled=True, flush_window_s=REPLAY_WINDOW_S)
    flushes0 = FLUSHES.total()
    t0 = time.monotonic()
    got = sched.agg_verify_many(table, bits_list, hs, sigs,
                                lane=sched.Lane.SYNC)
    wall = time.monotonic() - t0
    flushes = FLUSHES.total() - flushes0
    sched.reset()
    check(flushes == 1, f"replay batch went out in {flushes:g} "
          "dispatches, not one")
    ref = [_ref_agg(points, b, h, s)
           for b, h, s in zip(bits_list, hs, sigs)]
    check(got == ref, "replay: device lanes differ from the reference at "
          f"{[i for i, (g, r) in enumerate(zip(got, ref)) if g != r]}")
    check(got == [i != bad for i in range(width)],
          f"replay: decisions {got}")
    say(f"replay {width} headers x {n_keys} keys: one dispatch in "
        f"{wall:.3f} s (flush window {REPLAY_WINDOW_S} s), "
        f"{width - 1} accepted, 1 rejected, all match the reference")
    guard.after("replay", moved=("batch_verify",))
    return width


def phase_single(seed: int, guard: Guard, width: int = SINGLE_WIDTH
                 ) -> int:
    import numpy as np

    from harmony_tpu import device as DV
    from harmony_tpu.ref import bls as RB
    from harmony_tpu.ref.hash_to_curve import hash_to_g2

    guard.start()
    rng = np.random.default_rng(seed + 2)
    keys = _keys(seed, "single", width)
    hs = [hash_to_g2(rng.bytes(32)) for _ in range(width)]
    sigs = [_g2mul()(h, k.scalar) for h, k in zip(hs, keys)]
    bad = width // 2
    sigs[bad] = _g2mul()(hs[bad], keys[bad].scalar + 1)
    pks = [k.pub.point for k in keys]
    t0 = time.monotonic()
    got = DV.verify_many_on_device(pks, hs, sigs)
    wall = time.monotonic() - t0
    ref = [RB.verify_hashed(p, h, s) for p, h, s in zip(pks, hs, sigs)]
    check(got == ref, f"single: device {got} != reference {ref}")
    check(got == [i != bad for i in range(width)], f"single: {got}")
    say(f"single {width} verifies in {wall:.3f} s: {width - 1} accepted, "
        "1 rejected, all match the reference")
    guard.after("single", moved=("verify",))
    return width


def phase_localnet(guard: Guard, n_nodes: int = LOCALNET_NODES,
                   keys_per_node: int = LOCALNET_KEYS_PER_NODE,
                   blocks: int = LOCALNET_BLOCKS) -> int:
    """tools/sched_smoke.py's in-process localnet with the real kernels:
    every node pumps on its own thread, consensus checks go through the
    shared scheduler to the device."""
    from harmony_tpu import device as DV
    from harmony_tpu import sched
    from harmony_tpu.chain.engine import Engine, EpochContext
    from harmony_tpu.core.blockchain import Blockchain
    from harmony_tpu.core.genesis import dev_genesis
    from harmony_tpu.core.kv import MemKV
    from harmony_tpu.core.tx_pool import TxPool
    from harmony_tpu.multibls import PrivateKeys
    from harmony_tpu.node.node import Node
    from harmony_tpu.node.registry import Registry
    from harmony_tpu.p2p import InProcessNetwork

    guard.start()
    DV.use_device(True)
    sched.reset()
    # one request per dispatch: the buckets warmed above serve every
    # check (a coalesced pair of proofs would need a batch program)
    sched.configure(enabled=True, max_batch=1)
    genesis, _, bls_keys = dev_genesis(n_keys=n_nodes * keys_per_node)
    ctx = EpochContext([k.pub.bytes for k in bls_keys])
    net = InProcessNetwork()
    nodes = []
    for i in range(n_nodes):
        chain = Blockchain(MemKV(), genesis,
                           engine=Engine(lambda s, e: ctx, device=True),
                           blocks_per_epoch=16)
        reg = Registry(blockchain=chain,
                       txpool=TxPool(genesis.config.chain_id, 0,
                                     chain.state),
                       host=net.host(f"node{i}"))
        own = bls_keys[i * keys_per_node:(i + 1) * keys_per_node]
        nodes.append(Node(reg, PrivateKeys.from_keys(own)))
    pumps = []
    t0 = time.monotonic()
    try:
        pumps = [n.run_forever(poll_interval=0.002, block_time=0.2,
                               phase_timeout=120.0) for n in nodes]
        while min(n.chain.head_number for n in nodes) < blocks:
            check(time.monotonic() - t0 < LOCALNET_TIMEOUT_S,
                  "localnet stalled: heads "
                  f"{[n.chain.head_number for n in nodes]}")
            time.sleep(0.05)
    finally:
        for n in nodes:
            n.stop()
        for p in pumps:
            p.join(timeout=30)
        sched.reset()
        DV.use_device(None)
    check(not any(p.is_alive() for p in pumps), "a node pump did not stop")
    heads = [n.chain.head_number for n in nodes]
    say(f"localnet {n_nodes} nodes x {keys_per_node} keys: heads {heads} "
        f"in {time.monotonic() - t0:.1f} s")
    guard.after("localnet", moved=("agg_verify", "verify"))
    return min(heads)


def run(seed: int) -> dict:
    """Every phase in order; returns the device record."""
    t0 = time.monotonic()
    device = phase_device()
    from harmony_tpu.ref import native as NB

    say("host reference: "
        + ("ref/native" if NB.available() else "ref/ bigint"))
    guard = Guard()
    stats = phase_warmup(guard)
    timings = {}
    decisions = 0
    for name, fn in (("quorum", lambda: phase_quorum(seed, guard)),
                     ("replay", lambda: phase_replay(seed, guard)),
                     ("single", lambda: phase_single(seed, guard))):
        t = time.monotonic()
        decisions += fn()
        timings[name] = round(time.monotonic() - t, 3)
    t = time.monotonic()
    phase_localnet(guard)
    timings["localnet"] = round(time.monotonic() - t, 3)
    say(f"smoke timings (not benchmark numbers): phases_s={timings} "
        f"decisions_checked={decisions} "
        f"programs_compiled={stats['compiled']} "
        f"programs_cached={stats['cached']} "
        f"compile_s={stats['compile_s']:.1f} "
        f"script_wall_s={time.monotonic() - t0:.1f}")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="FBFT quorum checks on one TPU chip (see module doc)")
    ap.add_argument("--seed", type=int, default=21,
                    help="seed of the generated keys and payloads")
    args = ap.parse_args(argv)
    if not (ROOT / "harmony_tpu").is_dir():
        print("chip_smoke: FAIL: no harmony_tpu package beside this "
              "script; run it from the root of a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    os.environ.pop("HARMONY_KERNEL_TWIN", None)  # the real kernels only
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harmony_tpu import aot

    aot.use_compile_cache()
    try:
        device = run(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: jaxlib's atexit has crashed after
    # thread-heavy runs (tests/conftest.py), after the result printed
    os._exit(code)
