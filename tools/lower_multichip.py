"""AOT-lower the FUSED multi-chip quorum check to StableHLO (VERDICT r3 #7).

The fused ``sharded_agg_verify`` program (parallel/mesh.py) cannot
EXECUTE on this box — no real mesh, and the 8-virtual-device CPU compile
of a pairing-shaped program exceeds any budget.  But
LOWERING is tracing + StableHLO emission — no LLVM, seconds — and the
emitted module carries every sharding annotation and collective the
partitioner will act on.  Checking the text into the repo and diffing it
in CI (tests/test_multichip_artifact.py) makes mesh, sharding and
collective regressions in parallel/mesh.py fail CI without needing an
n-chip mesh.

Run:  python tools/lower_multichip.py [--check]
  writes (or with --check, diffs against)
  tools/artifacts/sharded_agg_verify_8dev.stablehlo.txt
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEV = 8
ARTIFACT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "artifacts",
    f"sharded_agg_verify_{N_DEV}dev.stablehlo.summary.txt",
)


def _normalize(text: str) -> str:
    """Strip volatile location/name noise so the diff is semantic."""
    text = re.sub(r"loc\([^)]*\)", "loc(-)", text)
    text = re.sub(r'#loc\d+ = .*', "", text)
    return text


def _summarize(text: str) -> str:
    """The structure this artifact guards, and nothing the JAX version
    renames: the public entry signature with its sharding attributes
    and every collective op with its shapes and replica groups (SSA
    value numbers blanked).  A change to the mesh, a sharding or the
    collective layout flips a line; private-function numbering and the
    module's exact text, which move with every JAX release, do not."""
    keep = []
    for ln in text.splitlines():
        s = ln.strip()
        if s.startswith("func.func public"):
            keep.append(s)
        elif ("all_gather" in s or "all_reduce" in s
              or "collective" in s or "all_to_all" in s
              or "psum" in s or "reduce_scatter" in s):
            keep.append(re.sub(r"%[\w#.]+", "%_", s)[:400])
    head = [
        f"# fused sharded_agg_verify lowering summary ({N_DEV} virtual devices)",
        "# regenerate: python tools/lower_multichip.py",
    ]
    return "\n".join(head + keep) + "\n"


def lower_text() -> str:
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={N_DEV}",
    )
    if "device_count" not in os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] += (
            f" --xla_force_host_platform_device_count={N_DEV}"
        )
    import jax

    # a lowering that never executes anything must not claim a chip
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from harmony_tpu.ops import interop as I
    from harmony_tpu.parallel import mesh as M
    from harmony_tpu.ref import bls as RB
    from harmony_tpu.ref.curve import g2
    from harmony_tpu.ref.hash_to_curve import hash_to_g2

    mesh = M.make_mesh(jax.devices()[:N_DEV])
    fn = M.sharded_agg_verify(mesh)

    # tiny fixture: 2 keys per device, exactly dryrun_multichip's shapes
    n_keys = 2 * N_DEV
    msg = b"aot-lowering-fixture-blockhash32"
    h = hash_to_g2(msg)
    sks = [RB.keygen(bytes([70 + i])) for i in range(n_keys)]
    pk_jac = jnp.asarray(
        np.stack(
            [I.g1_affine_to_jacobian_arr(RB.pubkey(sk)) for sk in sks]
        )
    )
    bitmap = jnp.ones(n_keys, dtype=jnp.int32)
    h_aff = jnp.asarray(I.g2_affine_to_arr(h))
    sig_aff = jnp.asarray(
        I.g2_affine_to_arr(g2.mul(h, 12345))  # any valid G2 point
    )
    lowered = fn.lower(pk_jac, bitmap, h_aff, sig_aff)
    return _summarize(_normalize(lowered.as_text()))


def main() -> int:
    text = lower_text()
    if "--check" in sys.argv:
        with open(ARTIFACT) as fh:
            want = fh.read()
        if text != want:
            import difflib

            diff = "\n".join(
                list(
                    difflib.unified_diff(
                        want.splitlines(),
                        text.splitlines(),
                        "checked-in",
                        "regenerated",
                        lineterm="",
                    )
                )[:120]
            )
            print(
                "STALE ARTIFACT: the fused multichip lowering changed.\n"
                "If intended, regenerate: python tools/lower_multichip.py\n"
                + diff
            )
            return 1
        print("artifact up to date")
        return 0
    os.makedirs(os.path.dirname(ARTIFACT), exist_ok=True)
    stale = os.path.join(
        os.path.dirname(ARTIFACT),
        f"sharded_agg_verify_{N_DEV}dev.stablehlo.txt",
    )
    if os.path.exists(stale):  # pre-summary full dump; don't vendor 22 MB
        os.remove(stale)
    with open(ARTIFACT, "w") as fh:
        fh.write(text)
    n_collectives = text.count("all_gather") + text.count("all_reduce")
    print(
        f"wrote {ARTIFACT}: {len(text.splitlines())} lines, "
        f"{n_collectives} collective op lines"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
