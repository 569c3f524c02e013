#!/usr/bin/env python3
"""Readings of the control, the reference with the configuration's guarantee
broken (``refs/<reference>.py``'s ``control``), at a cell's own size.

    python bench/control.py --workload kron.fofof --seeds 11,12,13

For each seed it makes the cell's tables as a run does and prints the gap
between the control's count and the reference's, which is what the run's
``count_gap`` would read with the control in the program's place.  Host
numpy only: it touches no device.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as bench


def control_gap(cell: bench.Cell, seed: int) -> dict:
    cfg = cell.config
    qspec = cfg["queries"][cell.traffic["query"]]
    gen, ref = bench.generator(cfg), bench.reference(cfg)
    tables = gen.make(cfg, bench.rng_for(seed, bench.STREAM_DATA))
    want = ref.count(tables, qspec)
    got = ref.control(tables, qspec, cfg["control"])
    return {"seed": seed, "control": cfg["control"], "reference": want,
            "control_count": got, "count_gap": abs(got - want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_gap(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
