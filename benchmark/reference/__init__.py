"""The benchmark's plain reference: BLS12-381 quorum-proof checks in pure
Python bigints.

The curve, field, pairing, hash-to-G2, point-encoding and Keccak modules
are copies of ``harmony_tpu/ref/`` with the native-library branches
taken out; ``check`` is the straightforward quorum-proof semantics.
Nothing here imports the program: later PRs may change the program,
never this yardstick.
"""
