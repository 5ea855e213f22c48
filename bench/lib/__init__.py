"""Shared yardstick of the on-chip benchmark: peaks, work counts, trace
reduction, device-side data, the plain reference and the harness core."""
