"""On-chip benchmark of the throughput predictor (``python3 bench/run.py``)."""
