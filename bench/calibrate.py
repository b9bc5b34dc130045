#!/usr/bin/env python3
"""Readings the check's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 13 \
        [--control] [--faults half_batch answer_altered] [--out FILE]

For each seed: the program's first three calls against the plain
reference (the lower readings), and, when asked, the control (the
reference computed one precision below the configuration's, in the
program's place) and each planted fault against the reference (the
upper readings). The reference is held against each side as a run holds
it against the program: near ties resolved by that side's own observed
models. No measured window: these readings need none. Each seed
prints one JSON line ``{"seed", "kind", "numbers"}`` to stdout and, with
``--out``, appends it to that file.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import importlib

    from bench import check, run
    spec, cell_entry, config, traffic = run.load_spec(args.workload)
    run.device_info(int(cell_entry["chips"]),
                    os.path.join(BENCH, "peaks.json"))
    run.enable_compile_cache()
    model = importlib.import_module("bench.models." + config["model"])
    tie = check.load_tie_margin(BENCH, args.workload)

    def emit(seed, kind, numbers, extra=None):
        row = {"seed": seed, "kind": kind, "numbers": numbers}
        row.update(extra or {})
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    def against(cell, side):
        """The reference as it is held against ``side``'s record."""
        return cell.reference_record(observed=side["observed"],
                                     tie_margin=tie)

    for seed in args.seeds:
        cell = model.Workload(config, traffic, seed)
        cell.build()
        cell.first_steps()
        prog = cell.prog
        cell.release()
        ref = against(cell, prog)
        emit(seed, "program", check.numbers(prog, ref),
             {"loss": prog["loss"], "ref_loss": ref["loss"],
              "margins": ref["margins"], "units": ref["units"],
              "resolved": ref["resolved"], "excluded": ref["excluded"],
              "worst_leaves": check.worst_leaves(prog, ref),
              "timings": cell.timings})
        if args.control:
            ctrl = cell.reference_record(control=True)
            ref_c = against(cell, ctrl)
            emit(seed, "control", check.numbers(ctrl, ref_c),
                 {"resolved": ref_c["resolved"],
                  "excluded": ref_c["excluded"],
                  "worst_leaves": check.worst_leaves(ctrl, ref_c)})
        # one model on the device at a time: a fault's run rebuilds the
        # same weights and data from the seed
        del cell
        gc.collect()
        for fault in args.faults:
            bad = model.Workload(config, traffic, seed, fault=fault)
            bad.build()
            bad.first_steps()
            bad.release()
            ref_b = against(bad, bad.prog)
            emit(seed, f"fault:{fault}", check.numbers(bad.prog, ref_b),
                 {"resolved": ref_b["resolved"],
                  "excluded": ref_b["excluded"],
                  "worst_leaves": check.worst_leaves(bad.prog, ref_b)})
            del bad
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
