"""On-chip serving benchmark: cells, traffic, metric readers and the
float32 reference that decides ``correct``.  Entry point: ``bench/run.py``."""
