#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate the system
sustains with no growing backlog. Run once, when a cell is defined; the
cell's traffic file then states a fixed rate.

    python bench/sweep.py --workload <cell> --seeds <n>,<m> --seconds <s> \\
        --rates 2,3,4,5,6

One set-up (with the first seed), then for each rate one window per
seed's traffic (the engine drained in between). Each window prints one
JSON line: offered and completed requests per second,
how long the last request took to finish after the window closed, and the
median due-to-first-token time of the window's first and second half; a
backlog that grows shows as a drain longer than one batch's life and a
second half slower than the first.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import generator, load_module  # noqa: E402
from bench.harness import drive, open_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    opened = open_cell(args.workload)
    if isinstance(opened, int):
        return opened
    _, cell, config, traffic, _ = opened
    if traffic["loop"] != "open":
        print("sweep: the cell's loop is closed; nothing to sweep",
              file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    sut = load_module("systems", config["system"]).System(config, traffic,
                                                          seeds[0])
    print(f"sweep: set-up {time.monotonic() - T_START:.1f} s", flush=True)
    try:
        for rate, seed in ((float(r), s) for r in args.rates.split(",")
                           for s in seeds):
            tr = dict(traffic, arrivals=dict(traffic["arrivals"],
                                             rate_per_s=rate))
            reqs = generator.schedule(tr, seed, args.seconds)
            records, (t0, t1), late = drive(sut, tr, reqs, args.seconds)
            done = [r for r in records if r.get("ok")]
            mid = t0 + args.seconds / 2
            halves = [[(r["first"] - r["due"]) * 1e3 for r in done
                       if (r["due"] < mid) == first] for first in (True, False)]
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "offered": len(records),
                "completed_per_s": len(done) / (t1 - t0),
                "tokens_per_s": sum(len(r["tokens"]) for r in done) / (t1 - t0),
                "drain_s": t1 - (t0 + args.seconds),
                "ttft_p50_ms_first_half": statistics.median(halves[0]) if halves[0] else None,
                "ttft_p50_ms_second_half": statistics.median(halves[1]) if halves[1] else None,
                "ttft_p90_ms": generator.percentile(
                    [(r["first"] - r["due"]) * 1e3 for r in done], 90),
                "failed": len(records) - len(done), "late_ms": late * 1e3}),
                flush=True)
    finally:
        sut.release()
        sut.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
