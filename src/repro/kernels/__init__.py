"""Pallas TPU kernels (checked on the CPU in Pallas' TPU interpret mode,
under tests only)."""
