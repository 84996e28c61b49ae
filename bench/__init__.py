"""On-chip serving benchmark: see README.md."""
