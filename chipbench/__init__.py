"""chipbench — the on-chip benchmark of tpu_dist (see chipbench/README.md)."""
