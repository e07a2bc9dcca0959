"""``kernels/ivf.batched_ivf_shortlist``: every query scores the int8 rows
of its ``nprobe`` probed buckets (``bcap`` slots each) with their packed
[scale; |g|^2; id] sidecar, writing partial distances and row ids."""

TRACE_NAMES = [r"shortlist"]


def cost(s):
    if s.get("mode") != "ivf":
        return None
    C, B, F = s["C"], s["B"], s["F"]
    rows = s["nprobe"] * s["bcap"]
    ops = 2 * C * B * rows * F
    nbytes = (C * B * rows * (F + 12)         # probed int8 rows + sidecar
              + 4 * C * B * F                 # queries
              + 4 * C * B * s["nprobe"]       # probe ids
              + 8 * C * B * rows)             # distances + ids out
    return ops, nbytes, "bf16_flops_per_s"
