"""chipbench — the on-chip benchmark's yardstick (see README.md here)."""
