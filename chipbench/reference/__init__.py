"""Plain references that decide ``correct``.

Straightforward numpy implementations of the semantics under test.  They
import nothing of the program and take nothing that it made: every input
(data, fleet parameters, seeds) comes from the benchmark's own generators.
"""
