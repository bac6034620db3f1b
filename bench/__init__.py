"""Chip benchmark of the DTI train and serve paths (``python3 bench/run.py``).

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<mix>.json`` (which names ``drivers/<kind>.py`` and its
generator ``traffic/<generator>.py``), ``metrics/<metric>.py`` and
``limits/<cell>.json``.
"""
