#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 [--fault start]

Runs the cell once per seed in one process (programs compile once), each
run as ``bench/run.py`` makes it (all its replicas, one per chip, and its
client processes), and prints one JSON line per seed: the
program's numbers (``program``) and those of the float32 control, the
reference put in the program's place (``control``). The benchmark's own
runs do not run the control. ``--fault start`` plants a fault first: every
GPHP fit returns its chain's start, whatever the rows say.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def plant_start_only() -> None:
    """Every GPHP fit returns its chain's start (the draws ignore the rows)."""
    import jax.numpy as jnp

    from repro.core.gp import params as gpparams
    from repro.core.suggest import BOSuggester

    fit = BOSuggester._fit_gphps

    def start_only(self, xj, yj, mj, chain_slot=None):
        out = fit(self, xj, yj, mj, chain_slot)
        start = gpparams.default_params(self.space.encoded_dim).pack()
        return jnp.broadcast_to(start, out.shape).astype(out.dtype)

    BOSuggester._fit_gphps = start_only


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", choices=("start",), default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from bench import run as bench_run
    from bench import spec

    if args.fault == "start":
        plant_start_only()

    bench_run.enable_cache()
    cell = spec.workload(args.workload)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        result = bench_run.run_cell(
            cell, cfg, mix, spec.limits(cell["name"]), seed, args.seconds,
            False, control=True,
            log=lambda msg: print(msg, file=sys.stderr, flush=True))
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "attempted": result["attempted"],
            "program": {k: c["value"] for k, c in result["checks"].items()},
            "control": result["control"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
