"""The program against the plain reference, the control, and the faults
that ``correct`` must catch, on tiny cells on the CPU."""

from __future__ import annotations

import pytest
from conftest import make_ctx

from chipbench import compare
from chipbench.calibrate import as_program
from chipbench.faults import FAULTS
from chipbench.forms import sweep as form
from chipbench.kinds import load_kind
from chipbench.reference.precision import BF16


def _parts(cfg):
    kind = load_kind(cfg["kind"])
    data = kind.make_data(cfg)
    fleet = form.make_fleet(cfg, kind)
    return kind, data, fleet


def test_program_matches_reference(tiny):
    cfg, limits = tiny
    kind, data, fleet = _parts(cfg)
    program = form.Program(cfg, form_traffic(), data, fleet, kind, 1)
    seed = form.sweep_seed(2**31 + 5, 1)
    got = program.sweep(seed)
    ref = form.reference_sweep(cfg, form_traffic(), kind.reference(data, cfg), fleet, seed)
    numbers = compare.sweep_numbers(got, ref, cfg["gap"])
    assert numbers["event_rel"] <= 1e-14
    assert compare.judge(numbers, limits)[0], numbers


def test_control_fails(tiny):
    """The reference one precision step lower, in the program's place."""
    cfg, limits = tiny
    kind, data, fleet = _parts(cfg)
    seed = form.sweep_seed(7, 1)
    ref = form.reference_sweep(cfg, form_traffic(), kind.reference(data, cfg), fleet, seed)
    ctl_problem = kind.reference(data, cfg, hi="float32", lo=BF16)
    ctl = form.reference_sweep(cfg, form_traffic(), ctl_problem, fleet, seed, hi="float32")
    numbers = compare.sweep_numbers(as_program(ctl), ref, cfg["gap"])
    assert numbers["event_rel"] > limits["event_rel"]
    assert not compare.judge(numbers, limits)[0]


def test_reference_pool_matches_the_serial_replay(tiny):
    """``calibrate.py``'s pool of workers gives the serial replay's runs,
    in the configuration's precision and one step lower: the event streams
    to the bit, the suboptimality to round-off in the precision it is
    computed in (a worker's BLAS runs one thread, so its sums take another
    order)."""
    import numpy as np

    from chipbench.calibrate import ReferencePool

    cfg, _ = tiny
    kind, data, fleet = _parts(cfg)
    seed = form.sweep_seed(2**31 + 29, 1)
    pool = ReferencePool(workers=2)
    try:
        for hi, precision, rtol in (("float64", {}, 1e-12),
                                    ("float32", {"hi": "float32", "lo": BF16}, 1e-5)):
            serial = as_program(form.reference_sweep(
                cfg, form_traffic(), kind.reference(data, cfg, **precision), fleet, seed, hi=hi))
            pooled = as_program(pool.sweep(cfg, form_traffic(), fleet, seed, precision, hi=hi))
            for name, runs in serial.items():
                for key, value in runs.items():
                    got, want = np.asarray(pooled[name][key]), np.asarray(value)
                    if key == "suboptimality":
                        np.testing.assert_allclose(got, want, rtol=rtol, err_msg=name)
                    else:
                        np.testing.assert_array_equal(got, want, err_msg=f"{name} {key}")
    finally:
        pool.close()


def form_traffic():
    from conftest import TRAFFIC

    return dict(TRAFFIC)


def test_sound_run_is_correct(tiny):
    cfg, limits = tiny
    out = form.run(make_ctx(cfg, limits))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "sim_iters_per_s"}


# -- faults planted in the timed path -----------------------------------------


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(tiny, fault, monkeypatch):
    cfg, limits = tiny
    FAULTS[fault](monkeypatch.setattr)
    out = form.run(make_ctx(cfg, limits))
    assert not out["correct"], out["checks"]


def test_traffic_cannot_set_the_device_count(tiny):
    """The scenario mesh follows the cell's chips, not the traffic."""
    cfg, limits = tiny
    kind, data, fleet = _parts(cfg)
    with pytest.raises(ValueError, match="num_devices"):
        form.Program(cfg, {**form_traffic(), "engine": {"num_devices": 4}}, data, fleet, kind, 1)
    assert form.Program(cfg, form_traffic(), data, fleet, kind, 1).engine.num_devices is None


def test_planted_faults_are_undone():
    from chipbench.faults import Patches

    real = form.Program.__init__
    patches = Patches()
    FAULTS["state_unchanged"](patches.setattr)
    assert form.Program.__init__ is not real
    patches.undo()
    assert form.Program.__init__ is real


def test_traffic_sets_sweep_size_and_engine(tiny):
    """A traffic file alone can widen the sweep or pick the kernel backend."""
    cfg, limits = tiny
    ctx = make_ctx(cfg, limits)
    ctx.traffic.update(methods=["sgd"], sweep={"scenarios": 3},
                       engine={"kernel_backend": "pallas"})
    out = form.run(ctx)
    assert out["correct"], out["checks"]
