#!/usr/bin/env python3
"""The control of ``correct``: the reference with its pairing check left
out (a validator that trusts any well-formed proof whose bitmap reaches
quorum), put in the program's place.  It breaks the guarantee each
config states (every decision equals the reference's), so every seed
has to come out not correct.

    python3 benchmark/tools/control.py --workload <cell> --calls <n> --seeds <a,b,c>

For each seed: the cell's pool at the cell's own size, the control's
decision for every request of ``--calls`` calls (as many as a run of
the cell makes), judged by the cell's driver as a run is.  Prints one JSON
line per seed.  It needs no device; runs of the benchmark never run it.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import gen  # noqa: E402
from benchmark import run as R  # noqa: E402


def control_run(cell, seed: int, calls: int, n_workers: int) -> dict:
    drv = R.driver_module(cell)
    fx = drv.prepare(cell.config, cell.traffic, seed, n_workers).result()
    pool = len(fx.items)
    ctl = gen.reference(fx, range(pool), n_workers, control=True)
    per_call = cell.traffic.get("batch", 1)
    decisions = [((k * per_call + j) % pool, ctl[(k * per_call + j) % pool])
                 for k in range(calls) for j in range(per_call)]
    checks = drv.judge(fx, decisions, n_workers)
    return dict(checks, correct=all(v <= 0 for v in checks.values()),
                decisions=len(decisions))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control_run(cell, seed, args.calls, R.workers())
        print(json.dumps(dict(out, workload=args.workload, seed=seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
