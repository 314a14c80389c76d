"""On-chip benchmark of ACE: see ``run.py``, and ``PERF.md`` at the root."""
