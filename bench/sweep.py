#!/usr/bin/env python3
"""Find the highest arrival rate a scheduled-traffic cell sustains.

    python3 bench/sweep.py --workload <cell> --seconds 51 \
        --rates 0.1 0.15 0.2 --seeds 5 6 7

Run once on the chip when a cell is defined; the rate the cell then
uses is written into its traffic file as a number.  One process: the
engine is set up once, then each rate and seed gets its own window of
the cell's traffic (the same lengths, arrivals at that rate in the
seed's order), the mix's drain, and a pause until the engine is idle.
Per window it prints one JSON line: requests due in the window, how
many had their first token by the window's end and by the drain's, the
time to first token (mean, median, 90th percentile), the queue wait
and the drain.

A rate is sustained when, on every seed, every request due in the
window has its first token by the end of the drain, and the median over
the seeds of the mean time to first token is at most twice the lowest
swept rate's: queueing has at most doubled what an idle engine takes.
The last line names the highest sustained rate, the knee.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from typing import Sequence

import run  # bench/run.py: sets up the import paths and the cache

import jax  # noqa: E402

from bench import drive, measured, traffic  # noqa: E402


def main(argv: Sequence[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; refusing to run", file=sys.stderr)
        return 2
    from repro.runtime.serve_loop import Request
    w = run.workload(args.workload, run.benchmark())
    files = run.cell_files(w)
    config, mix = files["config"], dict(files["mix"])
    _, dims, _, engine, _ = run.set_up(config, args.seeds[0])
    level, knee, below = {}, None, None
    for rate in sorted(args.rates):
        mix["rate_per_s"] = rate
        means, served_all = [], True
        for seed in args.seeds:
            client = drive.Client(engine, Request, dims["V"], seed)
            specs = traffic.stream(mix, seed, args.seconds)
            start, send_end, end = drive.poisson_window(
                client, specs, args.seconds, mix["drain_cap_s"])
            r = measured.Run(w, config, mix, dims, {}, 1,
                             list(client.records.values()), start, send_end,
                             end, 0.0)
            due = r.due_in_window()
            ttft = r.ttft(due)
            first = sum(1 for x in due if x.stamps)
            served_all &= first == len(due)
            means.append(statistics.fmean(ttft))
            print(json.dumps({
                "rate": rate, "seed": seed, "due": len(due),
                "first_token_by_window_end": sum(
                    1 for x in due if x.stamps and x.stamps[0] <= send_end),
                "first_token_by_drain_end": first,
                "ttft_mean_ms": 1000 * means[-1],
                "ttft_p50_ms": 1000 * drive.percentile(ttft, 50),
                "ttft_p90_ms": 1000 * drive.percentile(ttft, 90),
                "queue_wait_mean_ms": 1000 * statistics.fmean(
                    r.queue_waits(due)),
                "itl_p95_ms": 1000 * (drive.percentile(r.itl_gaps(), 95)
                                      or 0.0),
                "drain_s": end - send_end}), flush=True)
            while client.inflight:          # idle before the next window
                client.step()
        level[rate] = statistics.median(means)
        sustained = served_all and level[rate] <= 2 * level[min(level)]
        if sustained and knee == below:
            knee = rate
        below = rate
        print(json.dumps({"rate": rate, "ttft_mean_ms_median_over_seeds":
                          1000 * level[rate], "served_all": served_all,
                          "sustained": sustained}), flush=True)
    print(json.dumps({"workload": w["name"], "knee_per_s": knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
