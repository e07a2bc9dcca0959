"""Find a serving cell's knee: offer rising fixed rates, each for a few
seconds, to one warmed-up system in one process, and report per rate the
offered and served rates, the latency tail, and whether the queue grew
(the tail of the window's second half against its first).

Usage (on the chip): python perf/sweep.py --workload <cell> --seed <n>
    --rates 500,1000,2000 [--seconds 5]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perf import harness
    from perf.systems import gallery
    from perf.traffic import gallery_data, pacer
    spec = harness.load_spec(args.workload)
    if harness.tpu_devices(spec.chips) is None:
        return 1
    harness.enable_cache()
    cfg = spec.config
    _, _, centres, _, batcher = gallery.build(spec, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched = pacer.poisson_schedule(args.seed + i, rate, args.seconds,
                                       cfg["n_clients"])
        qs, _ = gallery_data.queries(args.seed + i, centres, sched.clients,
                                     cfg["gallery"]["id_rho"])
        run = pacer.run_open_loop(batcher, sched, qs, args.seconds)
        lat = run.latency * 1e3
        half = len(lat) // 2
        print(json.dumps({
            "rate_qps": rate, "offered": len(lat),
            "served_qps": run.answered_by(run.t_end) / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "p99_first_half_ms": float(np.percentile(lat[:half], 99)),
            "p99_second_half_ms": float(np.percentile(lat[half:], 99)),
            "launches": len(run.launches),
            "mean_fill": float(np.mean([l.slots for l in run.launches])),
            "service_ms": 1e3 * float(np.mean(
                [l.t_done - l.t_launch for l in run.launches])),
            "lag_p99_ms": 1e3 * float(np.percentile(run.lag, 99))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
