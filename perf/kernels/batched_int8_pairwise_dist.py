"""``kernels/int8_dist.batched_int8_pairwise_dist``: (C, B, F) f32 queries
against (C, G, F) int8 rows with per-row scale and squared norm, writing
(C, B, G) f32 squared distances."""

TRACE_NAMES = [r"^batched_int8_pairwise_dist(\.\d+)?$", r"i8dist"]


def cost(s):
    if s.get("mode") != "int8":
        return None
    C, B, G, F = s["C"], s["B"], s["G"], s["F"]
    ops = 2 * C * B * G * F
    nbytes = C * G * F + 8 * C * G + 4 * C * B * F + 4 * C * B * G
    return ops, nbytes, "bf16_flops_per_s"
