"""Pins for the HLO-text cost model (analysis/hlo.py, analysis/roofline.py).

The module's whole reason to exist is that XLA-CPU's ``cost_analysis()``
counts while-loop bodies once; these tests pin the loop-aware numbers on a
committed, hand-written HLO fixture (``tests/data/scan_allreduce.hlo``: a
5-trip while whose body runs a 16x16x16 dot and a 4-way all-reduce, plus a
fusion outside the loop).  Every expected value below is derived by hand
from the fixture so a regression in parsing, trip resolution, or the
byte/FLOP accounting shows up as an exact-number diff, not drift.
"""

import math
from pathlib import Path

import pytest

from repro.analysis import hlo, roofline
from repro.configs.base import ModelConfig, ShapeConfig

FIXTURE = Path(__file__).parent / "data" / "scan_allreduce.hlo"

# hand-derived fixture constants
TRIPS = 5
DOT_FLOPS = 2 * 16 * 16 * 16  # 8192 per trip
TABLE_BYTES = 16 * 16 * 4  # 1024, one f32[16,16] buffer
# per-trip body HBM bytes: counter add (2*4) + dot operand reads (2*1024)
# + dot result (2*1024) + all-reduce result (2*1024)
BODY_BYTES = 8 + 2 * TABLE_BYTES + 2 * TABLE_BYTES + 2 * TABLE_BYTES
ENTRY_BYTES = TRIPS * BODY_BYTES + 2 * TABLE_BYTES  # + the fusion result
# 4-way ring all-reduce: 2 * (n-1)/n * payload, once per trip
WIRE_BYTES = TRIPS * 2.0 * 3 / 4 * TABLE_BYTES


@pytest.fixture(scope="module")
def text():
    return FIXTURE.read_text()


class TestParse:
    def test_computations_and_entry(self, text):
        comps, entry = hlo.parse_computations(text)
        assert entry == "main"
        assert sorted(comps) == ["add", "body", "cond", "fused", "main"]

    def test_operands_resolved(self, text):
        comps, _ = hlo.parse_computations(text)
        body = comps["body"]
        assert body.by_name["y"].op == "dot"
        assert body.by_name["y"].operands == ["x", "x"]
        assert comps["main"].by_name["w"].operands == ["init"]

    def test_parameters_have_no_operands(self, text):
        comps, _ = hlo.parse_computations(text)
        assert comps["body"].by_name["state"].operands == []


class TestLoopMultiplicities:
    def test_while_body_counts_per_trip(self, text):
        comps, entry = hlo.parse_computations(text)
        mult = hlo.loop_multiplicities(comps, entry)
        assert mult == {"main": 1.0, "fused": 1.0, "body": float(TRIPS)}

    def test_follow_calls_false_skips_fusion_bodies(self, text):
        comps, entry = hlo.parse_computations(text)
        mult = hlo.loop_multiplicities(comps, entry, follow_calls=False)
        assert mult == {"main": 1.0, "body": float(TRIPS)}


class TestAnalyzeHlo:
    def test_flops_multiply_by_trip_count(self, text):
        cost = hlo.analyze_hlo(text)
        assert cost.flops == TRIPS * DOT_FLOPS

    def test_hbm_bytes(self, text):
        cost = hlo.analyze_hlo(text)
        assert cost.bytes == ENTRY_BYTES

    def test_collective_totals(self, text):
        cost = hlo.analyze_hlo(text)
        assert cost.coll_counts == {"all-reduce": float(TRIPS)}
        assert cost.coll_result_bytes["all-reduce"] == TRIPS * TABLE_BYTES
        assert cost.total_operand_bytes == TRIPS * TABLE_BYTES
        assert cost.total_wire_bytes == WIRE_BYTES

    def test_top_costs_ranked_by_trip_weighted_bytes(self, text):
        top = hlo.top_costs(text, k=3)
        # the per-trip dot and all-reduce results dominate at 2*1024*5
        assert top["bytes"][0][0] == 2 * TABLE_BYTES * TRIPS
        assert top["bytes"][0][1] == "body"
        assert len(top["collectives"]) == 1
        wire, comp_name, op, _ = top["collectives"][0]
        assert (wire, comp_name, op) == (WIRE_BYTES, "body", "all-reduce")

    def test_sxs_buffer_bytes_trip_weighted(self, text):
        # square f32[16,16] buffers: fusion result (1x) + dot and
        # all-reduce results inside the loop (5x each)
        expect = 2 * TABLE_BYTES * (1 + 2 * TRIPS)
        assert hlo.sxs_buffer_bytes(text, min_dim=16) == expect
        assert hlo.sxs_buffer_bytes(text) == 0.0  # default 1024 floor


def _tiny_model():
    return ModelConfig(
        name="t",
        family="dense",
        num_layers=1,
        d_model=8,
        num_heads=2,
        num_kv_heads=2,
        d_ff=16,
        vocab_size=32,
    )


class TestRoofline:
    SHAPE = ShapeConfig("train_4k", 4096, 256, "train")

    def test_dominant_term_collective(self, text):
        r = roofline.derive(_tiny_model(), self.SHAPE, 1000, {}, text, 4, roofline.V5E)
        assert r.flops_per_device == TRIPS * DOT_FLOPS
        assert r.bytes_per_device == ENTRY_BYTES
        assert math.isclose(r.compute_s, TRIPS * DOT_FLOPS / roofline.PEAKS[roofline.V5E].flops)
        assert math.isclose(r.memory_s, ENTRY_BYTES / roofline.PEAKS[roofline.V5E].hbm_bw)
        assert math.isclose(r.collective_s, WIRE_BYTES / roofline.PEAKS[roofline.V5E].link_bw)
        # the fixture's wire term is the largest of the three
        assert r.dominant == "collective"
        assert r.step_time_s == r.collective_s

    def test_dominant_term_memory_without_collective(self, text):
        # same graph with the all-reduce demoted to a copy: identical HBM
        # traffic, zero wire bytes -> the memory term must win
        variant = text.replace(
            "all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add",
            "copy(%y)",
        )
        r = roofline.derive(_tiny_model(), self.SHAPE, 1000, {}, variant, 4, roofline.V5E)
        assert r.bytes_per_device == ENTRY_BYTES
        assert r.collective_s == 0.0
        assert r.dominant == "memory"
        assert r.step_time_s == r.memory_s

    def test_unknown_device_kind_raises(self, text):
        with pytest.raises(ValueError, match="no published peaks"):
            roofline.derive(_tiny_model(), self.SHAPE, 1000, {}, text, 4, "cpu")

    def test_model_flops_and_mfu(self, text):
        r = roofline.derive(_tiny_model(), self.SHAPE, 1000, {}, text, 4, roofline.V5E)
        mf = 6.0 * 1000 * 4096 * 256 / 4  # 6ND train, per device
        assert math.isclose(r.model_flops_per_device, mf)
        assert math.isclose(
            r.useful_flops_fraction, mf / (TRIPS * DOT_FLOPS)
        )
        assert math.isclose(
            r.mfu, (mf / roofline.PEAKS[roofline.V5E].flops) / r.step_time_s
        )
