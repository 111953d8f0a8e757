"""Chip benchmark of the DSAG reproduction (see ``run.py`` and PERF.md).

Everything here is the yardstick: traffic generation, the plain
references that decide ``correct``, the trace reduction, the table of
peaks and the FLOP counts.  The program under test lives in ``src/`` and
is imported only by the forms (``forms/``) that drive it.
"""
