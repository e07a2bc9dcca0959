"""Axis context threading mesh-axis names through model code.

The same forward/backward code runs in two regimes:
  * unsharded (CPU smoke tests, small federated benchmarks): ``AxisCtx()``
    with all axis names None -> every collective helper is a no-op.
  * inside ``shard_map`` over the production mesh: axis names are the mesh
    axis strings and the helpers emit real ``jax.lax`` collectives.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from jax import lax

from repro.common.compat import pcast_varying


@dataclasses.dataclass(frozen=True)
class AxisCtx:
    """Names of mesh axes as seen from inside shard_map (None = unsharded)."""

    tp: Optional[str] = None      # tensor/expert parallel axis ("model")
    dp: Optional[str] = None      # data / client parallel axis ("data")
    pod: Optional[str] = None     # cross-pod data axis ("pod")
    fsdp: bool = False            # shard params over dp, all-gather on use
    dp2: Optional[str] = None     # extra batch axis (small-model dp layout:
                                  # the "model" axis carries batch instead)
    decode_ws: bool = False       # weight-stationary decode (no FSDP weight
                                  # gathers; activations move instead)

    @property
    def tp_size(self) -> int:
        return lax.axis_size(self.tp) if self.tp else 1

    @property
    def dp_size(self) -> int:
        return lax.axis_size(self.dp) if self.dp else 1

    def tp_index(self):
        return lax.axis_index(self.tp) if self.tp else 0

    # ---- collective helpers (no-ops when unsharded) ----
    def psum_tp(self, x):
        return lax.psum(x, self.tp) if self.tp else x

    @property
    def dp_axes(self):
        return tuple(a for a in (self.dp, self.pod, self.dp2) if a)

    def psum_dp(self, x):
        axes = self.dp_axes
        return lax.psum(x, axes) if axes else x

    def pmean_dp(self, x):
        axes = self.dp_axes
        return lax.pmean(x, axes) if axes else x

    def all_gather_param(self, w, axis: int):
        """FSDP weight gather: params stored sharded over dp on ``axis``."""
        if self.fsdp and self.dp:
            return lax.all_gather(w, self.dp, axis=axis, tiled=True)
        return w

    def vary(self, x):
        """Mark a literal (scan-carry init etc.) as device-varying over all
        mapped axes — required by shard_map's vma checking, which is what
        makes psum transpose correctly in grad."""
        axes = tuple(a for a in (self.tp, self.dp, self.pod, self.dp2) if a)
        if not axes:
            return x
        return pcast_varying(x, axes)

    def vary_dp(self, x):
        """Vary over the data/pod axes only. Needed for batch-replicated
        decode of FSDP models: gathered weights make layer outputs formally
        data-varying, so the scan carry must start data-varying too."""
        axes = self.dp_axes
        if not axes:
            return x
        return pcast_varying(x, axes)


UNSHARDED = AxisCtx()
