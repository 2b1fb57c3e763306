"""Tests of the benchmark itself (CPU; outside the repository tier-1 suite)."""
