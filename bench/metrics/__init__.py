"""Per-layer metric readers: ``bench/metrics/<metric>.py`` defines ``read(ctx) -> float | None``."""
