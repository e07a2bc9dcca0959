"""``kernels/relevance_aggregate.fused_relevance_aggregate``: Eq. 5
post-processing and Eq. 6, (C, C) raw relevance and (C, P) parameters in,
(C, P) bases and (C, C) normalised relevance out.

The TPU compiler names a Pallas kernel's instruction after the jitted
function that holds its call: ``fused_relevance_aggregate.1``, whether
``ops.fused_relevance_aggregate`` is dispatched alone (the stacked server
round) or inside another program."""

TRACE_NAMES = [r"^fused_relevance_aggregate(\.\d+)?$", r"_fused_kernel"]


def cost(s):
    if "P" not in s:
        return None
    C, P = s["C"], s["P"]
    ops = 2 * C * C * P
    nbytes = 8 * C * C + 8 * C * P
    return ops, nbytes, "bf16_flops_per_s"
