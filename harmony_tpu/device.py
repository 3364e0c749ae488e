"""The device-path switch: one knob deciding whether verification
choke points (FBFT proofs, view-change aggregates, engine seal checks)
run on the TPU ops or the host bigint twin.

The reference has no such switch — herumi IS its only path; here the
host bigint layer (ref/) is the portable fallback and the TPU ops
(ops/) are the production path.  Default is AUTO: device when JAX's
default backend is an accelerator, host under the CPU-only test image
(tests/conftest.py pins JAX_PLATFORMS=cpu, so the suite keeps its
cached-executable-friendly host route automatically).

COUNTERS record how many checks executed on device — a localnet run
can ASSERT the flagship path is live (VERDICT r1: the ops were dead
code in the shipped binary).
"""

from __future__ import annotations

import threading
import time

from . import aot
from . import faultinject as FI
from . import prof
from . import trace
from .log import get_logger
from .metrics import Gauge, Histogram, LockedCounters
from .resilience import CircuitBreaker

_log = get_logger("device")

_FORCED: bool | None = None
_AUTO: bool | None = None

COUNTERS = LockedCounters(
    "verify", "agg_verify", "batch_verify", "ref_fallback"
)

# Observability singletons (exposed through metrics.Registry alongside
# COUNTERS): per-dispatch latency, host<->device transfer bytes, and
# the jit program-shape cache — was this dispatch's (kernel, bucket)
# shape already compiled in-process, and how long did the compiling
# first dispatch take?  All annotated onto the active trace span too,
# so /debug/trace shows WHY one dispatch in a round cost 100x.
DISPATCH_SECONDS = Histogram(
    "harmony_device_dispatch_seconds",
    "wall time of one breaker-guarded device dispatch",
)
TRANSFER = LockedCounters("h2d", "d2h")
JIT = LockedCounters("hit", "miss")
JIT_COMPILE_SECONDS = Gauge(
    "harmony_device_jit_compile_seconds",
    "wall time of the first (compiling) dispatch per program shape",
)

_SEEN_PROGRAMS: set = set()
_SEEN_LOCK = threading.Lock()


def _program_first_use(program: str) -> bool:
    """True exactly once per program shape per process — the dispatch
    that pays the JIT compile (or the twin's first wire-up)."""
    with _SEEN_LOCK:
        first = program not in _SEEN_PROGRAMS
        if first:
            _SEEN_PROGRAMS.add(program)
    JIT.inc("miss" if first else "hit")
    return first


def mark_warm(program: str) -> None:
    """aot.warmup's hook: record ``program`` as already compiled (or
    twin-wired) so serving-path dispatches account a warm cache instead
    of paying a first-use compile.  No JIT counter movement — warmup is
    neither a hit nor a serving-path miss."""
    with _SEEN_LOCK:
        _SEEN_PROGRAMS.add(program)

# The device-dispatch circuit breaker: a backend that keeps raising (a
# failing accelerator, a dying sidecar of the twin kernels, an
# injected chaos fault) trips it OPEN and every check routes straight
# to the reference host path until a half-open probe re-admits the TPU.
# Consensus keeps finalizing on the slow-but-correct path instead of
# stalling — the fail-fast contract the FBFT layer assumes.
BREAKER = CircuitBreaker("device", failure_threshold=5,
                         reset_timeout_s=30.0)

# Optional per-dispatch latency budget (seconds).  None disables the
# check — the CPU test image legitimately takes seconds per eager
# pairing, so only deployments (and chaos tests) arm it.  A dispatch
# that completes but overruns the budget still returns its (correct)
# result; it is counted as a breaker failure so a consistently slow
# backend trips OPEN and later checks skip the wait entirely.
DISPATCH_DEADLINE_S: float | None = None


def set_dispatch_deadline(seconds: float | None) -> None:
    global DISPATCH_DEADLINE_S
    DISPATCH_DEADLINE_S = seconds


def _guarded(kind: str, dispatch, fallback):
    """Run one device dispatch under the breaker.

    Raise -> breaker failure + reference fallback (transparent: the
    caller still gets a correct bool).  Deadline overrun -> breaker
    failure, device result kept.  Breaker OPEN -> fallback without
    touching the device at all.  The whole attempt (fallback included,
    when one runs) is a ``device.dispatch`` trace span nested under
    whatever consensus/sidecar span caused it."""
    if not BREAKER.allow():
        COUNTERS.inc("ref_fallback")
        with trace.span("device.dispatch", component="device",
                        kind=kind, outcome="breaker_open"):
            return fallback()
    t0 = time.monotonic()
    with trace.span("device.dispatch", component="device", kind=kind):
        try:
            FI.fire("device.dispatch")
            out = dispatch()
        except Exception as e:  # noqa: BLE001 — any backend failure
            # degrades to the host path, never up into consensus
            BREAKER.record_failure()
            COUNTERS.inc("ref_fallback")
            _log.warn("device dispatch failed; reference fallback",
                      kind=kind, error=str(e))
            trace.annotate(outcome="ref_fallback", error=str(e))
            DISPATCH_SECONDS.observe(time.monotonic() - t0)
            return fallback()
        elapsed = time.monotonic() - t0
        DISPATCH_SECONDS.observe(elapsed)
        if (DISPATCH_DEADLINE_S is not None
                and elapsed > DISPATCH_DEADLINE_S):
            BREAKER.record_failure()
            _log.warn("device dispatch exceeded deadline", kind=kind,
                      budget_s=DISPATCH_DEADLINE_S)
            trace.annotate(outcome="deadline_overrun")
        else:
            BREAKER.record_success()
        return out

# Committee tables are padded to one of these pinned sizes so every
# epoch/committee shares a small set of compiled programs (pad keys are
# affine (0,0) = infinity, masked off by zero bitmap bits).
COMMITTEE_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024)


# graftlint: bucket-fn registry=COMMITTEE_BUCKETS
def committee_bucket(n: int) -> int:
    """Smallest pinned bucket admitting ``n`` committee slots.  Widths
    past the largest bucket raise instead of minting an unbounded
    program-shape family (the old round-up tail was exactly the
    NEWVIEW-wedge class GL15 now rejects): no deployed committee
    exceeds 1024 slots, and admitting one is a REGISTRY change —
    extend COMMITTEE_BUCKETS so the warmup manifest precompiles it."""
    for b in COMMITTEE_BUCKETS:
        if n <= b:
            return b
    raise ValueError(
        f"committee width {n} exceeds the largest pinned bucket "
        f"{COMMITTEE_BUCKETS[-1]}; extend COMMITTEE_BUCKETS (and "
        f"regenerate the compile manifest) to admit it")


class CommitteeTable:
    """A committee's pubkeys as ONE device-resident padded affine tensor
    — the epoch-keyed table of SURVEY §7.3 that lets steady-state quorum
    checks ship only a bitmap + 96-byte signature to the device."""

    def __init__(self, points):
        import numpy as np

        from .ops import interop as I

        self.n = len(points)
        self.size = committee_bucket(max(self.n, 1))
        # the original reference points are kept (cheap: references
        # only) so a failing backend can fall back to the host bigint
        # path without re-deriving them from the device layout
        self.points = list(points)
        arr = np.zeros((self.size, 2, 32), dtype=np.int32)
        if self.n:
            arr[: self.n] = I.g1_batch_affine(points)
        self._np = arr
        self._dev = None

    def device_array(self):
        if kernel_twin_active():
            return self._np  # twins are numpy-native; keep jax unloaded
        import jax.numpy as jnp

        if self._dev is None:
            self._dev = jnp.asarray(self._np)
            # the one table upload this cache exists to amortize —
            # count it so /metrics shows the epoch-boundary spike
            TRANSFER.inc("h2d", self._np.nbytes)
        return self._dev

    def pad_bits(self, bits):
        import numpy as np

        out = np.zeros((self.size,), dtype=np.int32)
        out[: self.n] = np.asarray(bits, dtype=np.int32)[: self.n]
        return out


_TABLE_CACHE: "dict[tuple, CommitteeTable]" = {}
_TABLE_CACHE_CAP = 8
_TABLE_CACHE_LOCK = threading.Lock()


def get_committee_table(serialized_keys, points) -> CommitteeTable:
    """Per-committee table cache: a fresh FBFT Validator is built every
    round, but the committee changes only at epoch boundaries — the
    host->device conversion must amortize across rounds, not re-run
    per block.  Keyed by the serialized key tuple; bounded (a node
    tracks at most its own + a few foreign committees at once).

    Locked: consensus, view-change and replay threads all reach this
    cache; eviction (pop during another thread's insert) must not race.
    The CommitteeTable build itself runs outside the lock — it is the
    expensive host->device conversion, and a duplicate build loses only
    work, not correctness."""
    key = tuple(serialized_keys)
    with _TABLE_CACHE_LOCK:
        tbl = _TABLE_CACHE.get(key)
    if tbl is None:
        tbl = CommitteeTable(points)
        with _TABLE_CACHE_LOCK:
            if (key not in _TABLE_CACHE
                    and len(_TABLE_CACHE) >= _TABLE_CACHE_CAP):
                _TABLE_CACHE.pop(next(iter(_TABLE_CACHE)))
            tbl = _TABLE_CACHE.setdefault(key, tbl)
    return tbl


def use_device(flag: bool | None):
    """Force the path (True/False) or restore AUTO (None)."""
    global _FORCED
    _FORCED = flag


def device_enabled() -> bool:
    """The forced path, else AUTO: the device path exactly when JAX's
    default backend is an accelerator.  A backend that fails to
    initialize raises here — it never silently selects the host."""
    global _AUTO
    if _FORCED is not None:
        return _FORCED
    if _AUTO is None:
        import jax

        _AUTO = jax.default_backend() != "cpu"
    return _AUTO


_VERIFY_BUCKET = 8
_verify_fn = None
_agg_verify_fn = None
_agg_verify_batch_fn = None


def kernel_twin_active() -> bool:
    """HARMONY_KERNEL_TWIN=1 swaps the XLA kernels for the bigint/
    native-backed twins (ops/twin.py): a LIVE node exercises every
    device-path layer — table padding, bitmap routing, COUNTERS, batch
    chunking — on hosts where XLA:CPU pairing execution is measured in
    minutes.  The kernel math stays covered by the ops parity tier."""
    import os

    return os.environ.get("HARMONY_KERNEL_TWIN") == "1"


def _kernels():
    if kernel_twin_active():
        from .ops import twin as T

        return T
    from .ops import bls as OB

    return OB


# The jit factories hold ONE jitted callable each; per-dispatch program
# selection (warmed AOT executable vs. plain jit) happens at the call
# sites through ``aot.resolve(program)``
# — the program NAME computed there is the single source of truth, so
# the compile-surface analysis (GL15) can derive every shape from the
# pinned bucket registries instead of chasing runtime ``.shape[0]``s.


def _get_verify_fn():
    global _verify_fn
    if kernel_twin_active():
        return _kernels().verify
    if _verify_fn is None:
        import jax

        from .ops import bls as OB

        _verify_fn = jax.jit(OB.verify)
    return _verify_fn


def _get_agg_verify_fn():
    global _agg_verify_fn
    if kernel_twin_active():
        return _kernels().agg_verify
    if _agg_verify_fn is None:
        import jax

        from .ops import bls as OB

        _agg_verify_fn = jax.jit(OB.agg_verify)
    return _agg_verify_fn


def _get_agg_verify_batch_fn():
    global _agg_verify_batch_fn
    if kernel_twin_active():
        return _kernels().agg_verify_batch
    if _agg_verify_batch_fn is None:
        import jax

        from .ops import bls as OB

        _agg_verify_batch_fn = jax.jit(OB.agg_verify_batch)
    return _agg_verify_batch_fn


_masked_sum_fn = None


def _get_masked_sum_fn():
    """One jitted masked tree-sum per process (shapes bucketed by the
    committee registry) — the fused path for accelerators.  The CPU
    route keeps the eager ops (same rationale as ``_fused``)."""
    global _masked_sum_fn
    if _masked_sum_fn is None:
        import jax

        from .ops import curve as CV

        _masked_sum_fn = jax.jit(
            lambda pks, bm: CV.masked_sum(pks, bm, CV.FP_OPS))
    return _masked_sum_fn


def _fused() -> bool:
    """One truly-fused jitted agg_verify program on real accelerators.
    On XLA:CPU every distinct jitted pairing-shaped program costs
    minutes of LLVM time on the test image, so the CPU route runs
    the SAME ops eagerly — op-by-op dispatch reuses small in-process
    kernel caches, the path the ops suite exercises in seconds.  Same
    math, same counters, zero big executables.  Twin kernels take the
    'fused' branch (they are plain python callables either way)."""
    if kernel_twin_active():
        return True
    import jax

    return jax.default_backend() != "cpu"


def _ref_agg_verify(table: CommitteeTable, bits, h_point,
                    sig_point) -> bool:
    """Host bigint twin of the fused quorum check — the fallback when
    the device backend is open-circuited or raised mid-dispatch."""
    from .ref import bls as RB
    from .ref.curve import g1

    agg = None
    for pt, bit in zip(table.points, bits):
        if bit:
            agg = g1.add(agg, pt)
    if agg is None:
        return False
    return RB.verify_hashed(agg, h_point, sig_point)


def agg_verify_on_device(table: CommitteeTable, bits, payload: bytes,
                         sig_point) -> bool:
    """THE fused FBFT quorum check: committee table resident on device,
    bitmap in, bool out — masked G1 tree-sum AND the 2-pairing product
    with no host affine round-trip (reference semantics:
    internal/chain/engine.go:619-642 in one shot).  Breaker-guarded:
    a raising or open-circuited backend degrades transparently to the
    reference host path."""
    from .ref.hash_to_curve import hash_to_g2

    with prof.stage("hash_to_g2"):
        h_point = hash_to_g2(payload)
    return agg_verify_hashed_on_device(table, bits, h_point, sig_point)


def agg_verify_hashed_on_device(table: CommitteeTable, bits, h_point,
                                sig_point) -> bool:
    """``agg_verify_on_device`` with the payload already hashed to G2 —
    the shape the scheduler submits (hash-to-curve runs on the
    submitting thread, never on the shared flush thread)."""
    h = h_point
    COUNTERS.inc("agg_verify")

    def dispatch() -> bool:
        import numpy as np

        from .ops import interop as I

        if kernel_twin_active():
            asarray = np.asarray
            OB = None  # twins only: jax stays unloaded
        else:
            import jax.numpy as jnp

            from .ops import bls as OB

            asarray = jnp.asarray
        fused = _fused()
        fn = _get_agg_verify_fn() if fused else OB.agg_verify
        bm = table.pad_bits(bits)
        hh = np.asarray(I.g2_affine_to_arr(h))
        sg = np.asarray(I.g2_affine_to_arr(sig_point))
        TRANSFER.inc("h2d", bm.nbytes + hh.nbytes + sg.nbytes)
        program = f"agg_verify_b{table.size}"
        if fused and not kernel_twin_active():
            warm = aot.resolve(program)
            if warm is not None:
                fn = warm
        first = _program_first_use(program) if fused else False
        t0 = time.monotonic()
        call_args = (
            table.device_array(), asarray(bm), asarray(hh), asarray(sg)
        )
        ok = fn(*call_args)
        res = np.asarray(ok)
        elapsed = time.monotonic() - t0
        if first:
            JIT_COMPILE_SECONDS.set(elapsed, program=program)
            prof.on_first_dispatch(program, fn, call_args, elapsed)
        else:
            prof.observe_execute(program, elapsed)
        TRANSFER.inc("d2h", res.nbytes)
        trace.annotate(
            program=program, bucket=table.size,
            jit_cache=("miss" if first else "hit") if fused else "eager",
            h2d_bytes=bm.nbytes + hh.nbytes + sg.nbytes,
            d2h_bytes=res.nbytes,
        )
        return bool(res)

    return _guarded("agg_verify", dispatch,
                    lambda: _ref_agg_verify(table, bits, h, sig_point))


def masked_pubkey_sum(points, bits, fallback, cache=None):
    """Masked Jacobian tree-sum of a pubkey list, breaker-guarded.

    The NEWVIEW adoption path aggregates a *candidate* mask's pubkeys
    — a mask that is not this node's own, so the committee-table
    bucket cache doesn't apply.  ``cache`` is an optional one-slot
    list holding the device-resident stacked point tensor across
    calls on the same mask (the CommitteeTable idiom without the
    bucket padding: masks own their width).

    This used to be the one device call outside guarded dispatch (the
    PR-15 pump-wedge class): a raising backend now degrades to the
    host ``fallback`` instead of surfacing into consensus, an OPEN
    breaker skips the device entirely, and the dispatch rides the
    same trace span / deadline accounting as every other kind.
    Callers keep the twin early-out (twins keep jax unloaded), but a
    twin activating between check and dispatch still falls back here
    rather than importing jax.
    """
    if kernel_twin_active():
        return fallback()
    COUNTERS.inc("masked_pubkey_sum")

    def dispatch():
        import jax.numpy as jnp
        import numpy as np

        from .ops import curve as CV
        from .ops import interop as I

        # pad mask and points to the committee bucket: one compiled
        # masked-sum program per PINNED width instead of one per mask
        # width (the PR-15 wedge minted a fresh program at every new
        # committee size).  Pad lanes carry zero bits, so the tree sum
        # selects infinity for them regardless of the pad values.
        width = committee_bucket(len(points))
        pks = cache[0] if cache is not None else None
        if pks is None:
            arr = np.zeros((width, 3, 32), dtype=np.int32)
            if points:
                arr[: len(points)] = np.stack(
                    [I.g1_affine_to_jacobian_arr(p) for p in points])
            pks = jnp.asarray(arr)
            if cache is not None:
                cache[0] = pks
        bm = np.zeros((width,), dtype=np.int32)
        bm[: len(points)] = np.asarray(bits, dtype=np.int32)
        TRANSFER.inc("h2d", bm.nbytes)
        program = f"masked_sum_w{width}"
        fused = _fused()
        fn = None
        if fused and not kernel_twin_active():
            fn = aot.resolve(program)
            if fn is None:
                fn = _get_masked_sum_fn()
        first = _program_first_use(program) if fused else False
        t0 = time.monotonic()
        if fn is not None:
            agg = fn(pks, jnp.asarray(bm))
        else:
            agg = CV.masked_sum(pks, jnp.asarray(bm), CV.FP_OPS)
        res = np.asarray(agg)
        elapsed = time.monotonic() - t0
        if first:
            JIT_COMPILE_SECONDS.set(elapsed, program=program)
        TRANSFER.inc("d2h", res.nbytes)
        trace.annotate(program=program, width=width,
                       jit_cache=("miss" if first else "hit")
                       if fused else "eager",
                       h2d_bytes=bm.nbytes, d2h_bytes=res.nbytes)
        return I.arr_to_g1_affine(res)

    return _guarded("masked_pubkey_sum", dispatch, fallback)


# Pinned batch widths for the replay path (same rationale as the
# committee buckets: a handful of compiled programs covers every batch
# size).  CPU caps at 64 — XLA:CPU's LLVM JIT struggles with the
# 256-wide pairing programs on the test image.
BATCH_BUCKETS_CPU = (8, 64)
BATCH_BUCKETS_TPU = (8, 64, 256)


# graftlint: bucket-fn registry=BATCH_BUCKETS_CPU,BATCH_BUCKETS_TPU
def batch_buckets() -> tuple:
    return BATCH_BUCKETS_TPU if device_enabled() else BATCH_BUCKETS_CPU


# graftlint: bucket-fn registry=BATCH_BUCKETS_CPU,BATCH_BUCKETS_TPU
def batch_bucket(n: int) -> int:
    for b in batch_buckets():
        if n <= b:
            return b
    return batch_buckets()[-1]


def agg_verify_batch_on_device(table: CommitteeTable, bits_list,
                               h_points, sig_points):
    """Replay-path batch: B quorum checks against one committee table,
    chunked to pinned batch widths — each chunk is ONE program (masked
    tree-sums + pairing checks together).  h_points are pre-hashed
    payload points (host hash-to-G2); returns list[bool].  Breaker-
    guarded like the single check: a backend failure anywhere in the
    batch re-runs the whole window on the reference host path."""

    def dispatch():
        import numpy as np

        from .ops import interop as I

        if kernel_twin_active():
            asarray = np.asarray
            OB = None  # twins only: jax stays unloaded
        else:
            import jax.numpy as jnp

            from .ops import bls as OB

            asarray = jnp.asarray
        results = []
        widest = batch_buckets()[-1]
        fused = _fused()
        fn = (_get_agg_verify_batch_fn() if fused
              else OB.agg_verify_batch)
        tbl = table.device_array()
        # dispatch EVERY chunk before syncing ANY result: a per-chunk
        # np.asarray inside this loop forced a device round-trip between
        # programs, serializing the replay pipeline exactly where the
        # batched verification should stream (GL07)
        pending = []  # (ok device array, live lane count)
        h2d = 0
        compiles = []  # (program, first-dispatch seconds)
        for start in range(0, len(bits_list), widest):
            chunk_bits = bits_list[start:start + widest]
            chunk_h = h_points[start:start + widest]
            chunk_s = sig_points[start:start + widest]
            n, padded = len(chunk_bits), batch_bucket(len(chunk_bits))
            sel = list(range(n)) + [0] * (padded - n)  # pad lanes sliced
            bm = np.stack([table.pad_bits(chunk_bits[i]) for i in sel])
            hh = np.asarray(I.g2_batch_affine([chunk_h[i] for i in sel]))
            sg = np.asarray(I.g2_batch_affine([chunk_s[i] for i in sel]))
            h2d += bm.nbytes + hh.nbytes + sg.nbytes
            program = f"agg_verify_batch_b{table.size}x{padded}"
            chunk_fn = fn
            if fused and not kernel_twin_active():
                warm = aot.resolve(program)
                if warm is not None:
                    chunk_fn = warm
            first = _program_first_use(program) if fused else False
            t0 = time.monotonic()
            call_args = (tbl, asarray(bm), asarray(hh), asarray(sg))
            ok = chunk_fn(*call_args)
            if first:
                compiles.append((program, time.monotonic() - t0))
                prof.on_first_dispatch(program, chunk_fn, call_args,
                                       time.monotonic() - t0)
            COUNTERS.inc("batch_verify")
            # a compiling chunk's drain time is compile, not execute —
            # it is recorded by on_first_dispatch, not the exec histo
            pending.append((ok, n, program, None if first else t0))
        TRANSFER.inc("h2d", h2d)
        d2h = 0
        for ok, n, program, t_issue in pending:
            # all programs are in flight; this loop only drains results
            flat = np.asarray(ok)  # graftlint: disable=GL07 reviewed: every chunk dispatched above, this is the drain
            # issue->drain latency per chunk: what "execute" means for
            # a streamed dispatch (includes queueing behind siblings)
            if t_issue is not None:
                prof.observe_execute(program, time.monotonic() - t_issue)
            d2h += flat.nbytes
            results.extend(bool(x) for x in flat[:n])
        TRANSFER.inc("d2h", d2h)
        for program, dur in compiles:
            JIT_COMPILE_SECONDS.set(dur, program=program)
        trace.annotate(
            chunks=len(pending), checks=len(bits_list),
            jit_compiles=len(compiles), h2d_bytes=h2d, d2h_bytes=d2h,
        )
        return results

    def fallback():
        return [
            _ref_agg_verify(table, bits, h, sig)
            for bits, h, sig in zip(bits_list, h_points, sig_points)
        ]

    return _guarded("batch_verify", dispatch, fallback)


def verify_on_device(pk_point, payload: bytes, sig_point) -> bool:
    """One aggregate check e(-G1, sig) e(pk, H(payload)) == 1 on the
    device, through the pinned-bucket batched verify (pads to 8 so the
    compiled program is shared with every other single check).

    pk_point: reference affine G1 point; sig_point: affine G2 point;
    payload: signed bytes (hash-to-G2 stays host-side per SURVEY §7.2).
    Breaker-guarded with a host bigint fallback like the fused paths.
    """
    from .ref.hash_to_curve import hash_to_g2

    with prof.stage("hash_to_g2"):
        h = hash_to_g2(payload)
    COUNTERS.inc("verify")

    def dispatch() -> bool:
        import numpy as np

        from .ops import interop as I

        if kernel_twin_active():
            asarray = np.asarray
            OB = None  # twins only: jax stays unloaded
        else:
            import jax.numpy as jnp

            from .ops import bls as OB

            asarray = jnp.asarray
        # fused: pad to the pinned bucket so one compiled program serves
        # every single check; eager (CPU): width 1, no padding — each
        # lane would re-run the whole pairing op-by-op.  Twin kernels
        # skip the padding: each lane costs a real host check
        fused = _fused()
        width = (_VERIFY_BUCKET
                 if fused and not kernel_twin_active() else 1)
        pk = np.asarray(I.g1_batch_affine([pk_point] * width))
        hh = np.asarray(I.g2_batch_affine([h] * width))
        sg = np.asarray(I.g2_batch_affine([sig_point] * width))
        TRANSFER.inc("h2d", pk.nbytes + hh.nbytes + sg.nbytes)
        program = f"verify_w{width}"
        fn = _get_verify_fn() if fused else OB.verify
        if fused and not kernel_twin_active():
            warm = aot.resolve(program)
            if warm is not None:
                fn = warm
        first = _program_first_use(program) if fused else False
        t0 = time.monotonic()
        call_args = (asarray(pk), asarray(hh), asarray(sg))
        ok = fn(*call_args)
        res = np.asarray(ok)
        elapsed = time.monotonic() - t0
        if first:
            JIT_COMPILE_SECONDS.set(elapsed, program=program)
            prof.on_first_dispatch(program, fn, call_args, elapsed)
        else:
            prof.observe_execute(program, elapsed)
        TRANSFER.inc("d2h", res.nbytes)
        trace.annotate(
            program=program, width=width,
            jit_cache=("miss" if first else "hit") if fused else "eager",
            h2d_bytes=pk.nbytes + hh.nbytes + sg.nbytes,
            d2h_bytes=res.nbytes,
        )
        return bool(res[0])

    def fallback() -> bool:
        from .ref import bls as RB

        return RB.verify_hashed(pk_point, h, sig_point)

    return _guarded("verify", dispatch, fallback)


def verify_many_on_device(pk_points, h_points, sig_points) -> list:
    """N *independent* single checks — distinct keys, distinct payload
    points — fused into pinned-width ``verify`` programs: the
    continuous-batching shape the scheduler feeds with coalesced
    tx-pool / RPC / sender-sig traffic (each of which used to pay a
    full dispatch round-trip alone).  h_points are pre-hashed payload
    G2 points.  Pad lanes are affine infinity (sliced off before
    return).  Breaker-guarded; fallback re-checks each lane on the
    host bigint path."""
    n_total = len(pk_points)
    COUNTERS.inc("verify", n_total)

    def dispatch():
        import numpy as np

        from .ops import interop as I

        if kernel_twin_active():
            asarray = np.asarray
            OB = None  # twins only: jax stays unloaded
        else:
            import jax.numpy as jnp

            from .ops import bls as OB

            asarray = jnp.asarray
        fused = _fused()
        fn = _get_verify_fn() if fused else OB.verify
        widest = batch_buckets()[-1]
        results = []
        # dispatch every chunk before syncing any result (the GL07
        # stream discipline agg_verify_batch_on_device established)
        pending = []  # (ok device array, live lane count)
        h2d = 0
        compiles = []  # (program, first-dispatch seconds)
        for start in range(0, n_total, widest):
            chunk_pk = pk_points[start:start + widest]
            chunk_h = h_points[start:start + widest]
            chunk_s = sig_points[start:start + widest]
            n = len(chunk_pk)
            padded = batch_bucket(n) if fused else n
            pad = padded - n
            pk = np.asarray(I.g1_batch_affine(chunk_pk))
            hh = np.asarray(I.g2_batch_affine(chunk_h))
            sg = np.asarray(I.g2_batch_affine(chunk_s))
            if pad:
                # pad with affine infinity: the twins short-circuit
                # those lanes and the kernels' pad output is sliced off
                pk = np.concatenate(
                    [pk, np.zeros((pad,) + pk.shape[1:], pk.dtype)]
                )
                hh = np.concatenate(
                    [hh, np.zeros((pad,) + hh.shape[1:], hh.dtype)]
                )
                sg = np.concatenate(
                    [sg, np.zeros((pad,) + sg.shape[1:], sg.dtype)]
                )
            h2d += pk.nbytes + hh.nbytes + sg.nbytes
            program = f"verify_w{padded}"
            chunk_fn = fn
            if fused and not kernel_twin_active():
                warm = aot.resolve(program)
                if warm is not None:
                    chunk_fn = warm
            first = _program_first_use(program) if fused else False
            t0 = time.monotonic()
            call_args = (asarray(pk), asarray(hh), asarray(sg))
            ok = chunk_fn(*call_args)
            if first:
                compiles.append((program, time.monotonic() - t0))
                prof.on_first_dispatch(program, chunk_fn, call_args,
                                       time.monotonic() - t0)
            pending.append((ok, n, program, None if first else t0))
        TRANSFER.inc("h2d", h2d)
        d2h = 0
        for ok, n, program, t_issue in pending:
            # all programs are in flight; this loop only drains results
            flat = np.asarray(ok)  # graftlint: disable=GL07 reviewed: every chunk dispatched above, this is the drain
            # issue->drain latency per chunk (see batch path above)
            if t_issue is not None:
                prof.observe_execute(program, time.monotonic() - t_issue)
            d2h += flat.nbytes
            results.extend(bool(x) for x in flat[:n])
        TRANSFER.inc("d2h", d2h)
        for program, dur in compiles:
            JIT_COMPILE_SECONDS.set(dur, program=program)
        trace.annotate(
            chunks=len(pending), checks=n_total,
            jit_compiles=len(compiles), h2d_bytes=h2d, d2h_bytes=d2h,
        )
        return results

    def fallback():
        from .ref import bls as RB

        return [
            RB.verify_hashed(pk, h, sig)
            for pk, h, sig in zip(pk_points, h_points, sig_points)
        ]

    return _guarded("verify_many", dispatch, fallback)
