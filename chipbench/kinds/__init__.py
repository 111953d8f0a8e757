"""Problem kinds: for each, the data generator, the plain reference, the
FLOP counts and the adapter that builds the program's problem object.

A configuration file names its kind; ``load_kind`` finds the module by
that name, so a new kind is a new file here.
"""

from __future__ import annotations

import importlib


def load_kind(name: str):
    return importlib.import_module(f"chipbench.kinds.{name}")
