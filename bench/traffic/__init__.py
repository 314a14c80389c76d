"""Traffic drivers (``<driver>.py``) and traffic mixes (``<mix>.json``)."""
