"""Readings that set a cell's limits: the program against the reference
on many seeds, the control (the reference one precision below the
configuration's) and planted faults on a few. One process, so set-up is
paid once per seed and compiles once.

Usage (on the chip, like ``run.py``):
  python perf/calibrate.py --workload <cell> --seeds 1,2,3 \
      [--control-seeds 4,5,6] [--seconds 4]

Prints one JSON line per reading: {"who": "program"|"control"|<fault>,
"seed": n, <number>: value, ...}. The benchmark's own runs never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def program_readings(spec, seeds, seconds, devices):
    from perf import harness
    for seed in seeds:
        clock = harness.CompileCounter()
        rec = harness.system(spec.config["system"]).run(
            spec, seed=seed, seconds=seconds, trace=False, clock=clock,
            t_start=time.perf_counter(), devices=devices)
        yield {"who": "program", "seed": seed, **rec.readings,
               "window_compiles": rec.layer.get("window_compiles"),
               **{k: v for k, (v, _) in rec.e2e.items()}}


def fleet_controls(spec, seeds):
    import jax.numpy as jnp

    from perf.reference import fleet as ref
    from perf.systems import fleet
    for seed in seeds:
        data = fleet.make_data(spec.config, spec.traffic, seed)
        span = {"first": spec.traffic["warmup_rounds"],
                "rounds": fleet.CHECK_ROUNDS}
        want = ref.run(spec.config, data, seed, **span)
        for who, kw in (("control", {"dtype": jnp.bfloat16}),
                        ("half_batch", {"half_batch": True})):
            got = ref.run(spec.config, data, seed, **span, **kw)
            yield {"who": who, "seed": seed, **ref.compare(got, want)}


def gallery_controls(spec, seeds, n=2048):
    import numpy as np

    from perf.reference import gallery as ref
    from perf.traffic import gallery_data, pacer
    cfg = spec.config
    C, G, k = cfg["n_clients"], cfg["gallery_rows"], cfg["k"]
    for seed in seeds:
        heads, rows, centres = gallery_data.make(seed, C, G, cfg["model"],
                                                 cfg["gallery"])
        sched = pacer.poisson_schedule(seed, n, 1.0, C)
        qs, _ = gallery_data.queries(seed, centres, sched.clients,
                                     cfg["gallery"]["id_rho"])
        rows = np.asarray(rows)
        ids, d = ref.serve(heads, rows, sched.clients, qs, k=k, bits=4)
        yield {"who": "control", "seed": seed,
               **ref.compare(heads, rows, sched.clients, qs, ids, d, k=k)}
        ids, d = ref.serve(heads, rows, sched.clients, qs, k=k, bits=8)
        yield {"who": "reference", "seed": seed,
               **ref.compare(heads, rows, sched.clients, qs, ids, d, k=k)}
        bad = ids.copy()
        bad[::7, k - 1] = (bad[::7, k - 1] + G // 2) % G   # answers altered
        yield {"who": "answer_altered", "seed": seed,
               **ref.compare(heads, rows, sched.clients, qs, bad, d, k=k)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perf import harness
    spec = harness.load_spec(args.workload)
    devices = harness.tpu_devices(spec.chips)
    if devices is None:
        return 1
    harness.enable_cache()
    system = spec.config["system"]
    for line in program_readings(spec, args.seeds, args.seconds, devices):
        print(json.dumps(line), flush=True)
    controls = fleet_controls if system == "fleet" else gallery_controls
    for line in controls(spec, args.control_seeds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
