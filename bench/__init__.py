"""DIFET's on-chip benchmark: one cell (a deployment under a traffic mix)
per run, driven by the data files under this directory (see ``run.py``)."""
