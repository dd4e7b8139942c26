"""On-chip benchmark of the streaming partitioners (see bench/README.md)."""
