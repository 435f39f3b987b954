"""chipbench — the on-chip benchmark of deepspeed_tpu (see README.md here and
BENCHMARK.json at the repository root)."""
