"""System drivers: ``run(spec, *, seed, seconds, trace, clock, t_start,
devices) -> harness.Record``."""
