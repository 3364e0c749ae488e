"""Readers shared by metrics of the same quantity in different cells.
Each takes a ``run.Run``: its window counters (deltas over the whole
window) or the reduction of its traced slice."""

from __future__ import annotations

import re

from benchmark import stats


def p50_ms(run):
    return 1000 * stats.percentile(run.samples, 50) if run.samples else None


def p95_ms(run):
    return 1000 * stats.percentile(run.samples, 95) if run.samples else None


def items_per_s(run):
    return stats.rate(run.items, run.elapsed) if run.items else None


def hash_to_g2_ms(run):
    """Host hash-to-G2 (the program's ``prof.stage("hash_to_g2")``), mean
    ms per payload hashed: one per check, one per replayed header."""
    n = run.counters.get("hash_to_g2_n", 0)
    return 1000 * run.counters["hash_to_g2_s"] / n if n else None


def sched_wait_ms(run):
    """Mean enqueue-to-dispatch wait in the scheduler
    (``harmony_sched_wait_seconds``), ms per request."""
    n = run.counters.get("sched_wait_n", 0)
    return 1000 * run.counters["sched_wait_s"] / n if n else None


def batch_fill_pct(run):
    """Live items over padded bucket slots across batched dispatches."""
    slots = run.counters.get("fill_slots", 0)
    return 100 * run.counters["fill_items"] / slots if slots else None


def _module(program: str) -> str:
    """The XLA module a manifest program runs as: ``agg_verify_b256``
    is ``jit_agg_verify``, ``agg_verify_batch_b256x64``
    ``jit_agg_verify_batch``."""
    return "jit_" + re.sub(r"_[bw]\d+(x\d+)?$", "", program)


def kernel_ms(run):
    """Device time of the cell's programs in the traced slice, ms per
    item (check or header) the slice decided."""
    progs = run.trace.get("programs", {})
    mods = {_module(p) for p in run.programs}
    t = sum(s for name, s in progs.items() if name in mods)
    return 1000 * t / run.traced_items if t and run.traced_items else None


def device_idle_pct(run):
    """1 - busy / window of the traced slice, in percent."""
    win = run.trace.get("window_s")
    return 100 * (1 - run.trace["busy_s"] / win) if win else None
