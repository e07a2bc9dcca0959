"""Operations one answered query requires, from its shapes: the head's
feature forward (two matmuls) and its distances — to every gallery row
(int8 mode), or to every centroid and the probed buckets' rows (ivf)."""


def query_ops(s) -> float:
    D, H, F = s["D"], s["H"], s["F"]
    feat = 2 * (D * H + H * F)
    if s["mode"] == "ivf":
        return feat + 2 * F * (s["nlist"] + s["nprobe"] * s["bcap"])
    return feat + 2 * F * s["G"]
