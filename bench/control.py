"""Read a cell's compared numbers for the program and for its control, on
several seeds in one process (the benchmark's own runs never do this).

    python3 bench/control.py --workload <name> --seeds 11,12,13 --seconds 5

For each seed: build, warm up and measure the cell as ``run.py`` does,
then compare the same retired requests twice: as the program answered
them, and as the control answers them (the reference one step below the
configuration's guarantee: the ``check`` of the cell's kind module).  One
JSON line per seed.  The control has to fail a number on every seed; the
program, none.
"""

import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.check import check_cell, passed  # noqa: E402
from bench.run import CompileCounter, log, measure, start_chip  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell_spec = spec.load_cell(args.workload)
    peaks = spec.load_peaks()
    devices = start_chip(cell_spec.chips, peaks)
    if devices is None:
        return 2
    compiles = CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        m = measure(cell_spec, seed, args.seconds, devices, peaks, compiles)
        program, _ = check_cell(m.cell, m.records, m.missing)
        control, _ = check_cell(m.cell, m.records, m.missing, control=True)
        line = {"workload": args.workload, "seed": seed, "compared": len(m.records),
                "program": {k: v for k, (v, _) in program.items()},
                "program_passed": passed(program),
                "control": {k: v for k, (v, _) in control.items()},
                "control_passed": passed(control)}
        log(json.dumps(line))
        print(json.dumps(line), flush=True)
        m = None
        gc.collect()  # the last seed's system and its device buffers, before the next
    return 0


if __name__ == "__main__":
    sys.exit(main())
