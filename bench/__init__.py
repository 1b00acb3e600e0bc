"""Chip benchmark of the repository: one cell per run of ``bench/run.py``.

Everything that belongs to one configuration, one traffic mix, one traffic
kind or one per-layer metric is a file of its own, found by name:

* ``configs/<name>.json`` (the sizes as run) and the plain reference that
  the file names beside it;
* ``traffic/<name>.json``: the parameters of one traffic mix, naming its
  ``kind``;
* ``kinds/<kind>.py``: the generator and the loop that drive the program;
* ``metrics/<name>.py``: the reader of one per-layer metric.
"""
