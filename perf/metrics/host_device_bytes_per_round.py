"""Round driver: bytes copied between host and device per round, both
ways: the ``h2d_bytes`` and ``d2h_bytes`` the program's spans declare
(from shapes), summed over the traced run's second part, over its
rounds."""
UNIT = "B"


def read(layer, spec):
    spans = layer.get("spans") or []
    rounds = sum(1 for e in spans if e["name"] == "round.gather")
    moved = [e.get("h2d_bytes", 0) + e.get("d2h_bytes", 0) for e in spans
             if "h2d_bytes" in e or "d2h_bytes" in e]
    if not rounds or not moved:
        return None
    return sum(moved) / rounds
