"""``kernels/quantize.batched_quantize`` in the codec's sparse encode: the
(C, K) kept values of every client, per-chunk symmetric int8. Element-wise
work on the vector unit: no operations count against a matmul peak, so the
roofline is the bytes moved."""

TRACE_NAMES = [r"^batched_quantize(\.\d+)?$", r"_quant_kernel"]


def cost(s):
    if "K" not in s:
        return None
    C, K, chunk = s["C"], s["K"], s["chunk"]
    nbytes = 4 * C * K + C * K + 4 * C * (-(-K // chunk))
    return 0, nbytes, "bf16_flops_per_s"
