"""One reader per per-layer or end-to-end metric: ``read(m) -> float | None``."""
