#!/usr/bin/env python3
"""Bare-kernel device bench: the first-device-hour command.

docs/PERF_MODEL.md §4 projects the as-written pairing kernel at
9k–21k pairings/s per chip; no chip run has checked it yet.  THIS is
the one command to run on the chip before any optimization lands:

    HARMONY_TPU_PROFILE_DIR=chiprun_out/tpu_prof python tools/bench_device.py

It (1) refuses to run without a TPU, (2) measures the BARE pairing
kernel (batch pairings/s — no consensus, no scheduler, just the
compiled program),
(3) checks the measurement against the modeled band and emits the
verdict machine-readably, (4) breaks the pipeline into its stages —
montmul, Miller loop, final exponentiation as separately-compiled
programs with a device sync between them, hash-to-G2 on host — into
the harmony_prof_* stage histograms, and (5) when
HARMONY_TPU_PROFILE_DIR is set, wraps the measured iterations in a
jax.profiler capture so a loadable trace exists after the FIRST
attempt (PERF_MODEL §6 step 3).

Every metric in the JSON line is tagged source: measured|modeled
(ISSUE 6 ledger discipline).  Without an accelerator the tool exits
non-zero — pairing-shaped programs take minutes to build on XLA:CPU
(--allow-cpu --stages montmul runs the one CPU-feasible stage, and
the record names the backend it ran on).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))

from bench import (  # noqa: E402 — repo root, via the path insert
    MODELED_BAND_PAIRINGS_S,
    _m,
    pairing_fixture,
)

ALL_STAGES = ("montmul", "miller_loop", "final_exp", "hash_to_g2")


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _time_calls(fn, warm_args, iters: int, stage: str, **attrs):
    """min-of-iters wall time of fn(*warm_args).  The compiling first
    call is excluded AND outside the prof stage: each timed iteration
    is its own harmony_prof_stage_seconds sample, so the stage
    breakdown compares EXECUTE time per stage — never compile time."""
    import jax

    from harmony_tpu import prof

    out = fn(*warm_args)
    jax.block_until_ready(out)
    best = None
    for _ in range(iters):
        t0 = time.perf_counter()
        with prof.stage(stage, **attrs):
            jax.block_until_ready(fn(*warm_args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def bench_stages(stages, batch: int, iters: int, extra: dict) -> None:
    """Per-stage breakdown: each pipeline stage as its own compiled
    program with a sync between stages — what the fused production
    program cannot show.  Results land in the prof stage histograms
    AND the tagged output."""
    import jax
    import numpy as np

    from harmony_tpu import prof
    from harmony_tpu.ops import fp as FP
    from harmony_tpu.ops import pairing as OP
    from harmony_tpu.ref.hash_to_curve import hash_to_g2

    if "montmul" in stages:
        # dense (B, 32) limb tiles — the §2 C_mul unit the whole model
        # prices; B wide enough to fill the VPU lanes
        rng = np.random.default_rng(3)
        width = max(batch, 256) * 16
        a = np.asarray(rng.integers(0, 1 << 12, (width, 32)), np.int32)
        b = np.asarray(rng.integers(0, 1 << 12, (width, 32)), np.int32)
        fn = jax.jit(FP.mont_mul)
        best = _time_calls(fn, (a, b), iters, "montmul", width=width)
        extra["montmul_per_sec"] = _m(
            round(width / best, 1), "mont_muls/s", width=width
        )

    needs_points = {"miller_loop", "final_exp"} & set(stages)
    if needs_points:
        ps, qs = pairing_fixture(batch)
        if "miller_loop" in stages:
            fn = jax.jit(OP.miller_loop)
            best = _time_calls(fn, (ps, qs), iters, "miller_loop",
                               batch=batch)
            extra["miller_loop_per_sec"] = _m(
                round(batch / best, 1), "miller_loops/s", batch=batch
            )
        if "final_exp" in stages:
            fs = OP.miller_loop(ps, qs)  # stage input, not timed
            fn = jax.jit(OP.final_exponentiation)
            best = _time_calls(fn, (fs,), iters, "final_exp",
                               batch=batch)
            extra["final_exp_per_sec"] = _m(
                round(batch / best, 1), "final_exps/s", batch=batch
            )

    if "hash_to_g2" in stages:
        # the host stage (SURVEY §7.2: branchy SHA work stays off the
        # accelerator) — its rate bounds ingress, not the kernel
        n = 16
        t0 = time.perf_counter()
        for i in range(n):
            with prof.stage("hash_to_g2"):
                hash_to_g2(b"bench-device-stage-%d" % i)
        extra["hash_to_g2_per_sec"] = _m(
            round(n / (time.perf_counter() - t0), 1), "hashes/s"
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--stages", default=",".join(ALL_STAGES),
                    help="comma list of stages to break down "
                         f"(default: {','.join(ALL_STAGES)})")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on XLA:CPU anyway (minutes per pairing "
                         "program; use --stages montmul,hash_to_g2)")
    ap.add_argument("--skip-pairing", action="store_true",
                    help="stages only — skip the bare e(P,Q) measure")
    args = ap.parse_args(argv)
    stages = [s for s in args.stages.split(",") if s]
    unknown = sorted(set(stages) - set(ALL_STAGES))
    if unknown:
        # a typo must not silently burn the one budgeted device hour
        # on a run with no stage breakdown
        ap.error(f"unknown stage(s) {unknown}; choose from "
                 f"{','.join(ALL_STAGES)}")

    lo, hi = MODELED_BAND_PAIRINGS_S
    out = {
        "metric": "bare_kernel_pairings_per_sec",
        "source": "measured",
        "extra": {
            "modeled_pairings_per_sec_lo": _m(lo, "pairings/s",
                                              "modeled",
                                              ref="docs/PERF_MODEL.md §4"),
            "modeled_pairings_per_sec_hi": _m(hi, "pairings/s",
                                              "modeled",
                                              ref="docs/PERF_MODEL.md §4"),
        },
        "meta": {},
    }
    extra = out["extra"]

    from harmony_tpu import aot

    aot.use_compile_cache()
    import jax

    dev = jax.devices()[0]
    out["meta"]["device"] = {"platform": dev.platform,
                             "kind": dev.device_kind,
                             "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"bench_device: no TPU (JAX found {dev.platform!r}); "
              "--allow-cpu runs the CPU-feasible stages",
              file=sys.stderr)
        return 1

    from harmony_tpu import prof

    prof.configure(enabled=True)
    capture_dir = prof.capture_dir()
    with prof.capture():
        if not args.skip_pairing:
            import numpy as np

            from harmony_tpu.ops import interop as I
            from harmony_tpu.ops import pairing as OP
            from harmony_tpu.ref import pairing as RP
            from harmony_tpu.ref.curve import G1_GEN, G2_GEN

            ps, qs = pairing_fixture(args.batch)
            fn = jax.jit(OP.pairing)
            t0 = time.perf_counter()
            first = fn(ps, qs)
            jax.block_until_ready(first)
            compile_s = time.perf_counter() - t0
            # correctness gate: a wrong kernel's throughput is noise
            assert I.arr_to_fp12(np.array(first[0])) == RP.pairing(
                G1_GEN, G2_GEN
            ), "device pairing result wrong!"
            best = None
            for _ in range(args.iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(ps, qs))
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            rate = args.batch / best
            out["value"] = round(rate, 1)
            out["unit"] = "pairings/s"
            extra["first_dispatch_seconds"] = _m(
                round(compile_s, 3), "s", batch=args.batch
            )
            extra["band_check"] = {
                "value": round(rate, 1), "unit": "pairings/s",
                "source": "measured", "band_lo": lo, "band_hi": hi,
                "in_band": bool(lo <= rate <= hi),
                "above_band": bool(rate > hi),
                "verdict": (
                    "in_band" if lo <= rate <= hi
                    else "above_band" if rate > hi
                    else "below_band_profile_before_optimizing"
                ),
            }
        bench_stages(stages, args.batch, args.iters, extra)

    if capture_dir:
        files = [
            os.path.join(r, f)
            for r, _, fs in os.walk(capture_dir) for f in fs
        ]
        out["meta"]["profile_dir"] = capture_dir
        out["meta"]["profile_files"] = len(files)
    out["meta"]["stage_summary"] = prof.stage_summary()
    _emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
