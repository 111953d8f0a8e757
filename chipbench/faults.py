"""Faults planted in the timed path, to show that ``correct`` catches them:
by the tests on the CPU, and by ``calibrate.py`` at a cell's own size on
the chip.  ``FAULTS`` break the sweep form's path, ``LIVE_FAULTS`` the
live form's; both name the same three faults.

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


# -- the live form: the program's Trainer, Tier-1 step and Tier-2 controller --


def live_state_unchanged(setattr):
    """A step that returns its state unchanged: the compiled step hands
    back the parameters it was given."""
    from repro.launch import train

    real = train.make_train_step

    def make_train_step(*a, **kw):
        step = real(*a, **kw)

        def frozen(state, *args):
            new, metrics = step(state, *args)
            return {**new, "params": state["params"]}, metrics

        return frozen

    setattr(train, "make_train_step", make_train_step)


def live_half_batch(setattr):
    """Half of the batch left out, the mean taken over the rest: every
    second group's gradient is dropped before the cache update, the others
    doubled."""
    import jax
    import jax.numpy as jnp

    from repro.core import dsag_pjit

    real = dsag_pjit.dsag_update

    def dsag_update(dsag, group_grads, mask, flush, evict=None):
        def half(g):
            keep = (jnp.arange(g.shape[0]) % 2 == 0).astype(g.dtype) * 2
            return g * keep.reshape((-1,) + (1,) * (g.ndim - 1))

        return real(dsag, jax.tree.map(half, group_grads), mask, flush, evict)

    setattr(dsag_pjit, "dsag_update", dsag_update)


def live_answer_altered(setattr):
    """An answer altered where it is produced: the Tier-2 controller flips
    group 0's mask bit on each job's second step."""
    from repro.ft.runtime import DeadlineController

    real = DeadlineController.step_inputs

    def step_inputs(self, *a, **kw):
        out = real(self, *a, **kw)
        self.fault_calls = getattr(self, "fault_calls", 0) + 1
        if self.fault_calls == 2:
            out.mask = out.mask.copy()
            out.mask[0] = not out.mask[0]
        return out

    setattr(DeadlineController, "step_inputs", step_inputs)


LIVE_FAULTS = {f.__name__.removeprefix("live_"): f
               for f in (live_state_unchanged, live_half_batch, live_answer_altered)}
FAULTS_OF_FORM = {"sweep": FAULTS, "live": LIVE_FAULTS}


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
