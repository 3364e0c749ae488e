#!/usr/bin/env python3
"""Record the small TPU trace that ``tests/test_trace_reduce.py`` reads.

    python3 benchmark/tools/record_trace.py <out.xplane.pb>   # on the chip

Three calls of a small jitted program inside a ``bench:slice`` span,
each call inside ``bench:entry``, with 20 ms of host work in a
``bench:hash_to_g2`` span before each: so the device idles about 60 ms
of the slice, charged to that span.  Not part of any run.
"""

import os
import shutil
import sys
import tempfile
import time
from pathlib import Path


def main(out: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x) + 1.0

    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    tdir = tempfile.mkdtemp(dir=Path(out).resolve().parent)
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench:slice"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:entry"):
                with jax.profiler.TraceAnnotation("bench:hash_to_g2"):
                    time.sleep(0.02)
                for _ in range(4):
                    x = step(x)
                x.block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(Path(tdir).rglob("*.xplane.pb"))[-1]
    shutil.copyfile(src, out)
    shutil.rmtree(tdir)
    print(f"record_trace: {out} {Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
