"""Plain references, one per system: straightforward jax.numpy in float32
at ``Precision.HIGHEST``, importing nothing of the program and taking
nothing it made. Each also holds the comparison that decides ``correct``."""
