#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run).

    python3 bench/calibrate.py --workload qwen0.5b-fl-thmin \
        --seeds 1,2,3 --control-seeds 1,2,3 --out chiprun_out/cal.jsonl

For each seed, in one process on the chip: the program's numbers at the
cell's own size (its first rounds through the timed loop, against the
plain reference: the lower readings); for each control seed also the
control's (the reference with fp8 matmuls in the program's place) and the
half-batch fault's (the reference with half of every step's rows left
out), both against the float32 reference: the upper readings.  One JSON
line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from harness import compare, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]

    cell = spec.resolve(args.workload)
    jax = run.setup_jax()
    from harness.device import require_chips
    require_chips(jax.devices(), cell.chips)
    sys.path.insert(0, str(run.ROOT / "src"))
    kind = spec.load_kind(cell.traffic["kind"])
    n = cell.traffic["check_rounds"]
    with open(args.out, "a") as out:
        for seed in sorted(set(seeds) | set(cseeds)):
            t0 = time.perf_counter()
            fed = kind.Federation(cell, seed)
            prog = kind.program_readings(fed, n)
            fed.free()
            ref = kind.reference_readings(fed, n, prog["theta"],
                                          prog["gossip"])
            rec = {"workload": cell.name, "seed": seed,
                   "device": jax.devices()[0].device_kind}
            if seed in seeds:
                rec["program"] = kind.check_numbers(prog, ref)
                rec["program_by_leaf"] = {
                    "grad": kind._rounded(compare.leaf_gaps(prog["grad"],
                                                            ref["grad"])),
                    "update": kind._rounded(compare.leaf_gaps(
                        prog["update"], ref["update"]))}
            if seed in cseeds:
                ctl = kind.reference_readings(fed, n, prog["theta"],
                                              prog["gossip"], lowp="fp8")
                rec["control_fp8"] = kind.check_numbers(ctl, ref)
                half = kind.reference_readings(
                    fed, n, prog["theta"], prog["gossip"],
                    rows=cell.traffic["seqs_per_step"] // 2)
                rec["fault_half_batch"] = kind.check_numbers(half, ref)
            rec["losses"] = {"program": prog["loss"], "reference":
                             ref["loss"]}
            rec["seconds"] = time.perf_counter() - t0
            line = json.dumps(rec)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
            del fed
    return 0


if __name__ == "__main__":
    sys.exit(main())
