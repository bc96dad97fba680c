"""Collects the benchmark's own tests (``benchmark/tests/``) in tier-1:
the yardstick that decides every PR is guarded by the suite it gates."""
