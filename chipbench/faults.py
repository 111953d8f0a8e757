"""Faults planted in the timed path, to show that ``correct`` catches them:
by the tests on the CPU, and by ``calibrate.py`` at a cell's own size on
the chip.

Each fault takes a ``setattr(obj, name, value)`` (pytest's
``monkeypatch.setattr``, or ``Patches.setattr``) and is planted before the
program is built.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def state_unchanged(setattr):
    """A step that returns its state unchanged: the program's update never
    moves the iterate."""
    from chipbench.forms import sweep as form

    real = form.Program.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        self.methods = {n: dataclasses.replace(m, eta=0.0) for n, m in self.methods.items()}

    setattr(form.Program, "__init__", init)


def half_batch(setattr):
    """Half of the batch left out, the mean taken over the rest: every
    second block subgradient of a batch is dropped, the others doubled."""
    from repro.core import problems

    real = problems.FusedKernels.__post_init__

    def post_init(self):
        inner = self.sub_blocks

        def sub_blocks(Vb, starts, widths, pad_width):
            out = inner(Vb, starts, widths, pad_width)
            keep = (np.arange(out.shape[0]) % 2 == 0).astype(np.float32) * 2.0
            return out * keep.reshape((-1,) + (1,) * (out.ndim - 1))

        self.sub_blocks = sub_blocks
        real(self)

    setattr(problems.FusedKernels, "__post_init__", post_init)


def answer_altered(setattr):
    """An answer altered where it is produced: one iteration's time of the
    scan's output moves by one part in a million."""
    from repro.experiments import fused

    real = fused.run_convergence_scan

    def scan(*a, **kw):
        out = real(*a, **kw)
        out.times = out.times.copy()
        out.times[0, -1] *= 1.0 + 1e-6
        return out

    setattr(fused, "run_convergence_scan", scan)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, answer_altered)}


class Patches:
    """``setattr`` that remembers, and ``undo`` that restores."""

    def __init__(self):
        self._undo = []

    def setattr(self, obj, name, value):
        self._undo.append((obj, name, obj.__dict__[name]))
        setattr(obj, name, value)

    def undo(self):
        while self._undo:
            obj, name, value = self._undo.pop()
            setattr(obj, name, value)
