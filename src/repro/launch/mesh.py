"""Production mesh builders (TPU v5e).

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
*before* any jax initialization; tests/benches must keep seeing 1 device.

Axis semantics (DESIGN.md §3):
  * "model": tensor/expert parallel within a pod row.
  * "data":  batch + federated-client parallel.
  * "pod":   cross-pod data/client parallel (pods = spatial regions of edge
    clients in the FedSTIL deployment story).
"""
from __future__ import annotations

from repro.sharding.specs import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(tp: int = 2, dp: int = 2, multi_pod: bool = False):
    """Small mesh for CI-scale sharding tests (8 host devices)."""
    if multi_pod:
        return auto_mesh((2, dp, tp), ("pod", "data", "model"))
    return auto_mesh((dp, tp), ("data", "model"))


# TPU v5e hardware constants (roofline §Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
