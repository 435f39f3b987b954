"""Found by name: one file per entry (see chipbench/README.md)."""
