#!/usr/bin/env python3
"""Readings of the program and of its control, seed after seed, in one
process: what the limits of the ``correct`` checks are set from.

    python bench/control.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13 [--control-seeds 11,12]

For each seed the cell is set up and driven for a short window at its own
load, as a run is, and the adapter's checks give the program's readings.
For a seed in ``--control-seeds`` the adapter's ``control`` also reads the
control: the plain reference put in the program's place, one step below
the configuration's precision, or (no precision stated) with one of its
guarantees broken. Each seed prints one JSON line. Benchmark runs never
run the control.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import generator, load_module  # noqa: E402
from bench.harness import drive, open_cell  # noqa: E402


def main(argv=None, *, allow_cpu: bool = False, overrides=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    opened = open_cell(args.workload, allow_cpu, overrides)
    if isinstance(opened, int):
        return opened
    _, cell, config, traffic, _ = opened
    system = load_module("systems", config["system"])
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        sut = system.System(config, traffic, seed)
        try:
            reqs = generator.schedule(traffic, seed, args.seconds)
            records, _, _ = drive(sut, traffic, reqs, args.seconds)
            sut.release()
            line = {"seed": seed, "attempted": len(records),
                    "failed": sum(1 for r in records if not r.get("ok")),
                    "program": {n: v for n, v, _ in sut.check(records)}}
            if seed in control_seeds:
                line["control"] = {n: v for n, v, _ in sut.control(records)}
        finally:
            sut.close()
        line["seconds"] = time.monotonic() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
