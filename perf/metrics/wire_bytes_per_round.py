"""Codec: measured wire bytes per client per round, both directions (the
per-client bytes the stacked codec reports from its encoded buffers, as
logged in ``CommLog``), over the window's rounds."""
UNIT = "B"


def read(layer, spec):
    return layer.get("wire_bytes_per_round")
