"""Chip benchmark of data-diffusion stacking (see PERF.md at the repository root)."""
