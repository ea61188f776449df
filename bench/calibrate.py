#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 11 12 13

For each seed, in one process: a whole run of the cell as
``bench/run.py`` makes it (weights from the seed, warm-up, the window at
the cell's own load, the drain), then the check's number twice on the
same sampled requests: as the program served them (the lower reading)
and as the control puts its own first tokens there (the reference
computed in float8; the upper reading).  The control's reading goes
through the cell's own verdict (``check.verdict`` with the limits of
``bench/cells/<cell>.json``), which has to come out not correct.
Benchmark runs never run the control.  One JSON line per seed, then the
extremes.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Sequence

import run  # bench/run.py: sets up the import paths and the cache

import jax  # noqa: E402

from bench import check  # noqa: E402


def main(argv: Sequence[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    bench = run.benchmark()
    w = run.workload(args.workload, bench)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU; refusing to run", file=sys.stderr)
        return 2
    limits = run.cell_files(w)["cell"]["limits"]
    served_gap, lows, highs = check.served_gap, [], []
    for seed in args.seeds:
        seen = {}

        def spy(ref, c, params, reqs):
            seen.update(ref=ref, c=c, params=params, reqs=reqs)
            return served_gap(ref, c, params, reqs)

        check.served_gap = spy
        try:
            out = run.run_cell(w, run.cell_files(w), seed, args.seconds,
                               False, devices[:w["chips"]], bench,
                               log=lambda m: print(m, file=sys.stderr))
        finally:
            check.served_gap = served_gap
        low = out["checks"]["max_logit_gap"]["value"]
        ctl = check.control_gap(seen["ref"], seen["c"], seen["params"],
                                seen["reqs"])
        lows.append(low)
        highs.append(ctl["max_logit_gap"])
        print(json.dumps({"seed": seed, "program": low,
                          "program_correct": out["correct"],
                          "control": ctl["max_logit_gap"],
                          "control_correct":
                              check.verdict(ctl, limits)["correct"],
                          "tokens": ctl["tokens"],
                          "longest_context": max(
                              len(r.prompt) + len(r.out)
                              for r in seen["reqs"]),
                          "metrics": out["metrics"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"]}),
              flush=True)
        del seen
    print(json.dumps({"workload": w["name"], "program_max": max(lows),
                      "control_min": min(highs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
