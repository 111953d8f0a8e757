"""The trace reduction on a hand-made trace, on an XSpace read through
JAX's own reader, and on a recorded slice of a chip trace."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"
SPAN = "chipbench.sweep"


def _rep():
    # device 0: op a [0,15] with b [5,10] nested in it, c [30,35];
    # device 1: a [0,20]
    return {
        "devices": [
            {"name": "/device:TPU:0",
             "ops": [("a", 0, 15), ("b", 5, 5), ("c", 30, 5)],
             "modules": [("jit__run_scan(7)", 0, 15), ("jit_other(3)", 30, 5)]},
            {"name": "/device:TPU:1", "ops": [("a", 0, 20)], "modules": []},
        ],
        "host": [{"line": "main", "events": [(SPAN, 0, 50), ("inner", 20, 8)]}],
    }


def test_union_and_window():
    assert trace_reduce.union([(5, 15), (0, 10), (30, 35)]) == [(0, 15), (30, 35)]
    red = trace_reduce.reduce(_rep(), SPAN)
    assert red["window_s"] == pytest.approx(50e-9)
    # busy 20 ns on device 0 and 20 on device 1: mean 20
    assert red["busy_s"] == pytest.approx(20e-9)
    assert red["span_s"] == pytest.approx(50e-9)
    assert red["span_busy_s"] == pytest.approx(20e-9)
    # module self time per executable name, (id) suffix dropped, averaged
    # over the two devices
    assert red["modules"] == pytest.approx({"jit__run_scan": 7.5e-9, "jit_other": 2.5e-9})


def test_breakdown():
    red = trace_reduce.reduce(_rep(), SPAN)
    # self time averaged over the two devices, named by executable
    assert dict(red["device_ops"]) == pytest.approx({
        "a in ?": 10e-9,  # device 1 has no module line
        "a in jit__run_scan(7)": 5e-9,  # 15 less the 5 of b nested in it
        "b in jit__run_scan(7)": 2.5e-9,
        "c in jit_other(3)": 2.5e-9,
    })
    assert red["device_ops"][0][0] == "a in ?"
    # gaps on device 0: [15, 30] inside "inner" at its midpoint, [35, 50]
    # in the span alone
    assert red["idle_gaps"] == [["inner", pytest.approx(15e-9)], [SPAN, pytest.approx(15e-9)]]


def test_no_span_reads_nothing():
    rep = _rep()
    rep["host"] = []
    red = trace_reduce.reduce(rep, SPAN)
    assert red["window_s"] == 0.0 and red["busy_s"] == 0.0


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__run_scan(12)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.sweep" } }
}
"""


def test_extract_through_jax_reader():
    from jax.profiler import ProfileData

    rep = trace_reduce.extract(ProfileData.from_text_proto(XSPACE))
    # line timestamp (ns) + offset (ps), durations from ps to ns
    assert rep["devices"][0]["ops"] == [("fusion.1", 2000, 3000), ("fusion.1", 7000, 2000)]
    assert rep["host"][0]["events"] == [(SPAN, 1000, 10000)]
    red = trace_reduce.reduce(rep, SPAN)
    assert red["busy_s"] == pytest.approx(5e-6)
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["modules"] == pytest.approx({"jit__run_scan": 9e-6})


def test_recorded_chip_slice():
    """5 ms of a traced sweep on a TPU v5e (pca_genomics_50w), and the
    numbers the reduction gave for it when it was recorded."""
    rec = json.loads((DATA / "chip_slice.json").read_text())
    red = trace_reduce.reduce(rec["trace"], SPAN)
    assert 0 < red["busy_s"] <= red["window_s"]
    for key, want in rec["reduced"].items():
        assert red[key] == pytest.approx(want), key
