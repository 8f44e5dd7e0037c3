"""Knee sweep of an open-loop cell: one set-up, then a window at each of
several offered rates, in one process on the chip.

    python3 -m bench.sweep --workload stablelm-12b.chat --seed 3 \
        --seconds 40 --rates 0.2,0.4,0.6

For each rate it prints the requests that arrived and completed, the
TTFT median and 95th percentile, the ITL 95th percentile, and the TTFT
median of the window's first and second halves of arrivals: where the
second is well above the first, the queue grew through the window and
the rate is past the knee.  The cell's rate is then set, by hand, to
about four fifths of the highest rate that kept pace.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from bench import run, serving, traffic


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    sys.path.insert(0, str(run.REPO / "src"))
    cell = serving.load_cell(args.workload)
    try:
        run.check_device(cell["entry"]["chips"])
    except run.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    from repro.configs.base import ModelConfig
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = ModelConfig(**cell["config"]["model"])
    q = serving.quantize(cfg)
    eng = serving.build(q, cell["workload"], annotate=False)
    serving.warm_up(eng, cfg.vocab_size, traffic.seed_streams(args.seed, 4)[2])
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell["workload"]["rate_per_s"] = rate
        w = serving.Window(eng, cell, args.seed + i, args.seconds)
        w.run()
        recs = sorted(w.window_recs(), key=lambda r: r.due)
        ttft = [r.times[0] - r.due for r in recs if r.times]
        half = len(recs) // 2
        first = [r.times[0] - r.due for r in recs[:half] if r.times]
        second = [r.times[0] - r.due for r in recs[half:] if r.times]
        gaps = [g for r in recs for g in np.diff(r.times)]
        row = {"rate": rate, "arrived": len(recs),
               "completed": sum(r.done is not None and
                                len(r.tokens) == r.max_new for r in recs),
               "drain_s": w.t_end - w.t0 - args.seconds,
               "ttft_p50_s": traffic.percentile(ttft, 50),
               "ttft_p95_s": traffic.percentile(ttft, 95),
               "ttft_first_half_p50_s": traffic.percentile(first, 50),
               "ttft_second_half_p50_s": traffic.percentile(second, 50),
               "itl_p95_s": traffic.percentile(gaps, 95)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": args.workload, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
