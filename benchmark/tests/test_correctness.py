"""What decides ``correct``: a sound run passes, and each fault a cell
can have, the control, and a host fallback all come out not correct.
Runs go past the look for a chip and drive the program's device path
with its twin kernels, at a size a test run holds."""

import os

import pytest

from conftest import SEED, drive, tiny_cell

from benchmark import gen
from benchmark.reference import check as C
from benchmark.tools import control

CELLS = ("v5_quorum_proof", "v3_3_replay_window")


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(twin, workload):
    out = drive(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"setup_s"}
    assert list(out)[-1] == "checks"


def _flip(fn, which):
    def altered(*args, **kwargs):
        out = fn(*args, **kwargs)
        if isinstance(out, list):
            return [not x if i == which else x for i, x in enumerate(out)]
        return not out
    return altered


@pytest.mark.parametrize("workload,producer", [
    ("v5_quorum_proof", "agg_verify_hashed_on_device"),
    ("v3_3_replay_window", "agg_verify_batch_on_device"),
])
def test_an_answer_altered_where_it_is_produced(twin, monkeypatch,
                                                workload, producer):
    from harmony_tpu import device as DV

    monkeypatch.setattr(DV, producer, _flip(getattr(DV, producer), 3))
    out = drive(workload)
    assert not out["correct"] and out["checks"]["mismatch"]["value"] > 0


def test_half_the_batch_left_out(twin, monkeypatch):
    """The replay window's second half accepted unchecked."""
    from harmony_tpu import device as DV

    real = DV.agg_verify_batch_on_device

    def half(table, bits, hs, sigs):
        k = len(bits) // 2
        return real(table, bits[:k], hs[:k], sigs[:k]) + [True] * (
            len(bits) - k)

    monkeypatch.setattr(DV, "agg_verify_batch_on_device", half)
    out = drive("v3_3_replay_window")
    assert not out["correct"] and out["checks"]["mismatch"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_quorum_always_reached(twin, monkeypatch, workload):
    """The program's stake-quorum check left out: the short proofs'
    valid signatures pass the device, and the run is not correct."""
    from harmony_tpu.consensus.quorum import Decider

    monkeypatch.setattr(Decider, "is_quorum_achieved_by_mask",
                        lambda self, bitmap: True)
    out = drive(workload, seconds=4.0)
    assert not out["correct"] and out["checks"]["mismatch"]["value"] > 0


def test_quorum_tallied_by_slots(twin, monkeypatch):
    """Slots counted instead of stake: a short proof's signers hold more
    than 2/3 of the slots where the Harmony slots carry 0.49 of the
    vote (mainnet V3_3; the tiny cell keeps its vote share)."""
    from harmony_tpu.consensus.quorum import Decider

    monkeypatch.setattr(Decider, "is_quorum_achieved_by_mask",
                        lambda self, bitmap: 3 * sum(map(bool, bitmap))
                        > 2 * len(bitmap))
    out = drive("v3_3_replay_window", seconds=4.0)
    assert not out["correct"] and out["checks"]["mismatch"]["value"] > 0


def test_no_native_host_library_no_result(twin, monkeypatch):
    """With the native host BLS library missing, hash-to-G2 would fall
    back to pure Python: the run fails instead of measuring that."""
    from benchmark import run as R
    from harmony_tpu.ref import native

    def broken():
        raise OSError("libharmony_bls381.so: cannot open")

    monkeypatch.setenv("HOST_BLS", "native")
    monkeypatch.setattr(native, "_avail", None)
    monkeypatch.setattr(native, "_load", broken)
    with pytest.raises(R.RunFailure, match="native host BLS"):
        drive("v5_quorum_proof", seconds=0.5)


def test_a_host_fallback_is_not_correct(twin, monkeypatch):
    """Right answers from the host reference are still not the device
    path: the guard fails the run."""
    from harmony_tpu import device as DV
    from harmony_tpu.ops import twin as T
    from harmony_tpu.resilience import CircuitBreaker

    monkeypatch.setattr(DV, "BREAKER", CircuitBreaker(
        "device", failure_threshold=5, reset_timeout_s=30.0))

    def broken(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(T, "agg_verify", broken)
    out = drive("v5_quorum_proof", seconds=1.0)
    assert out["checks"]["mismatch"]["value"] == 0
    assert out["checks"]["ref_fallback"]["value"] > 0
    assert not out["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(workload):
    out = control.control_run(tiny_cell(workload), SEED, 16, 2)
    assert out["mismatch"] > 0 and not out["correct"]
    assert out["reference_vs_made"] == 0


def test_the_reference_agrees_with_the_programs_own():
    """benchmark/reference is a copy: on the same proofs it decides as
    harmony_tpu's host reference does, and hashes headers alike."""
    from harmony_tpu.chain.header import Header
    from harmony_tpu.ref import bls as RB
    from harmony_tpu.ref import native as NB

    if not NB.available():
        pytest.skip("native host BLS library not built")
    cell = tiny_cell("v3_3_replay_window")
    fx = gen.Pending(cell.config, cell.traffic, SEED, 2).result()
    ref = gen.reference(fx, range(len(fx.items)), 2)
    for i, it in enumerate(fx.items):
        assert Header(version="v3", **it.header).hash() == it.block_hash
        sig = RB.sig_from_bytes(it.proof[:96])
        apk = RB.aggregate_pubkeys(
            [RB.pubkey_from_bytes(pk) for pk, b in zip(fx.pubkeys, it.bits)
             if b])
        assert RB.verify(apk, it.payload, sig) == (
            it.invalid not in ("forged_signature", "bitmap_mismatch"))
        assert ref[i] == (not it.invalid)
    assert C.bits(fx.items[0].proof[96:], fx.slots) == fx.items[0].bits


@pytest.mark.parametrize("workload", CELLS)
def test_the_programs_quorum_agrees_at_full_size(workload):
    """At the cells' own committee and stakes, on a dozen seeds, the
    program's stake tally (``Decider.is_quorum_achieved_by_mask``) finds
    a quorum in every proof but the short ones, as the reference's
    exact tally does: the host half of the new invalid kind, checked
    where a chip run checks it only on a few seeds."""
    from fractions import Fraction

    from benchmark import run as R
    from benchmark.drivers._committee import roster
    from harmony_tpu.consensus.quorum import Decider, Policy

    cell = R.load_cell(workload)
    for seed in range(SEED, SEED + 12):
        fx, _ = gen.plan(cell.config, cell.traffic, seed)
        fx.pubkeys = [i.to_bytes(48, "big") for i in range(fx.slots)]
        d = Decider(Policy.STAKED, fx.pubkeys, roster(cell.config, fx))
        for it in fx.items:
            ref = sum(p for p, b in zip(fx.power, it.bits) if b) > Fraction(2, 3)
            assert d.is_quorum_achieved_by_mask(it.bits) == ref == (
                it.invalid != "short_of_quorum")


def test_the_reference_imports_nothing_of_the_program():
    import ast
    from pathlib import Path

    ref = Path(C.__file__).parent
    for f in ref.glob("*.py"):
        tree = ast.parse(f.read_text())
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                names = [a.name for a in node.names] + [
                    getattr(node, "module", None) or ""]
                assert not any("harmony_tpu" in n for n in names), f
    assert os.path.basename(ref) == "reference"
