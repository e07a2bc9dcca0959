"""Operations one steady-state federated round requires, from its shapes,
counting no recomputation: every client's local training (forward,
weight gradients, and input gradients of the layers above the first) over
``epochs`` minibatches of ``rows``, the rehearsal selection's feature
forward over its ``n_train`` prototypes, and the Eq. 6 aggregate. The
codec, ring and relevance are element-wise or tiny."""


def round_ops(s) -> float:
    m = s["model"]
    p, h, f, n = m["proto_dim"], m["hidden"], m["feat_dim"], m["n_classes"]
    fwd = 2 * (p * h + h * f + f * n)
    train_row = 2 * fwd + 2 * (h * f + f * n)
    C = s["C"]
    return (C * s["epochs"] * s["rows"] * train_row
            + C * s["n_train"] * 2 * (p * h + h * f)
            + 2 * C * C * s["P"])
