"""``kernels/pairwise_dist.batched_pairwise_dist`` in the batched eval:
(C, Q, D) f32 query features against (C, G, D) gallery features, writing
(C, Q, G) f32 squared distances."""

TRACE_NAMES = [r"^batched_pairwise_dist(\.\d+)?$", r"_bdist_kernel"]


def cost(s):
    if "eval_Q" not in s:
        return None
    C, Q, G, D = s["C"], s["eval_Q"], s["eval_G"], s["eval_D"]
    ops = 2 * C * Q * G * D
    nbytes = 4 * C * Q * D + 4 * C * G * D + 4 * C * Q * G
    return ops, nbytes, "bf16_flops_per_s"
