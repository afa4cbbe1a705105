"""The trace reduction on a small trace recorded from the chip.

data/tbase_s4096_two_steps.events.json.gz is two of the four steps a
scratch probe traced at transformer_base B=4 S=4096 on 1 x TPU v5 lite
(my chip run, PR 25): the "XLA Ops" events of the device plane (Pallas
events with their whole instruction text, others cut to their head) and
the host spans of the thread that carried the step annotation. The
figures asserted were read by hand, with a plain sweep and plain regular
expressions outside benchmark/lib/trace.py.
"""
import gzip
import json
import os

import pytest

from benchmark.families import transformer_encdec as family
from benchmark.lib import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tbase_s4096_two_steps.events.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        raw = json.load(f)
    (plane, events), = raw["devices"].items()
    (_, spans), = raw["host"].items()
    return [T.Op(*e) for e in events], [T.Op(*s) for s in spans]


def _classify(op):
    return family.classify_kernel(*T.signature(op), op.name)


def test_busy_union_and_idle_share(recorded):
    ops, _ = recorded
    assert len(ops) == 21458
    # no two op events overlap on this line, so union == sum here; the
    # union still has to be what a sweep gives
    assert T.busy_seconds(ops) == pytest.approx(0.458524594, rel=1e-9)
    span = (max(o.end_ns for o in ops) - min(o.start_ns for o in ops)) / 1e9
    assert span == pytest.approx(0.470913667, rel=1e-9)
    assert 100 * (1 - T.busy_seconds(ops) / span) == pytest.approx(
        2.6308, abs=1e-3)


def test_union_counts_overlap_once():
    ops = [T.Op("a", 0, 100), T.Op("b", 50, 100), T.Op("c", 60, 10),
           T.Op("d", 300, 50), T.Op("zero", 400, 0)]
    assert T.busy_intervals(ops) == [[0, 150], [300, 350]]
    assert T.busy_seconds(ops) == pytest.approx(200e-9)


def test_kernel_event_selection(recorded):
    ops, _ = recorded
    pallas = [o for o in ops if T.is_pallas(o)]
    assert len(pallas) == 342                 # 171 a step, as the HLO has
    kinds = [_classify(o) for o in pallas]
    adam = [o for o, k in zip(pallas, kinds) if k == "fused_adam"]
    flash = [o for o, k in zip(pallas, kinds) if k == "flash_attention"]
    assert len(adam) == 198 and len(flash) == 144
    assert sum(o.dur_ns for o in adam) / 1e6 == pytest.approx(4.638235)
    assert sum(o.dur_ns for o in flash) / 1e6 == pytest.approx(341.370345)
    rest = sum(o.dur_ns for o in ops if not T.is_pallas(o)) / 1e6
    assert rest == pytest.approx(112.516014)


def test_hbm_bytes_leave_out_on_chip_operands(recorded):
    ops, _ = recorded
    adam = [o for o in ops if T.is_pallas(o)
            and _classify(o) == "fused_adam"]
    assert sum(T.hbm_bytes(o) for o in adam) == 2403336640
    text = ('%step1.9 = (f32[8,128]{1,0:T(8,128)S(1)}, f32[8,128]{1,0:T(8,128)})'
            ' custom-call(f32[1,4]{1,0:T(1,128)S(1)} %a, f32[8,128]{1,0:T(8,128)}'
            ' %b, bf16[8,128]{1,0:T(8,128)(2,1)} %c), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={f32[1,4]{1,0}}')
    assert T.hbm_bytes(T.Op(text, 0, 1)) == 8 * 128 * (4 + 4 + 2)
    assert T.signature(T.Op(text, 0, 1)) == (
        ["f32[1024]", "f32[1024]"], ["f32[4]", "f32[1024]", "bf16[1024]"])


def test_rooflines_of_the_recorded_steps(recorded):
    """The readers on the recorded steps: flash 18.39% of 197 TFLOP/s,
    fused_adam 63.27% of 819 GB/s (by hand)."""
    from benchmark.lib import cells, peaks
    ops, spans = recorded
    sz = family.sizes(json.load(open(os.path.join(
        cells.BENCH, "configs", "transformer_base.json"))))
    tr = json.load(open(os.path.join(cells.BENCH, "traffic",
                                     "fixed_b4_s4096.json")))
    kernels = {}
    for o in ops:
        if T.is_pallas(o):
            kernels.setdefault(_classify(o), []).append(o)
    ctx = {"trace": {"kernels": kernels, "n_devices": 1}, "steps": 2,
           "family": family, "sizes": sz, "traffic": tr, "chips": 1,
           "peaks": peaks.peaks("TPU v5 lite"), "trace_lib": T}
    assert cells.layer_metric_reader("flash_attn_roofline_pct")(ctx) == \
        pytest.approx(18.3933, abs=1e-3)
    assert cells.layer_metric_reader("fused_adam_roofline_pct")(ctx) == \
        pytest.approx(63.2671, abs=1e-3)
    ctx["trace"]["kernels"] = {}
    assert cells.layer_metric_reader("flash_attn_roofline_pct")(ctx) is None
    assert cells.layer_metric_reader("fused_adam_roofline_pct")(ctx) is None


def test_idle_gaps_go_to_the_innermost_host_span(recorded):
    ops, spans = recorded
    gaps = T.idle_gaps(ops, spans)
    # the longest gap by hand: 6.710373 ms between two steps
    assert max(b[0] - a[1] for a, b in zip(T.busy_intervals(ops),
                                           T.busy_intervals(ops)[1:])) \
        == pytest.approx(6.710373e6)
    assert sum(gaps.values()) <= 0.470913667 - 0.458524594 + 1e-9
    assert "PjitFunction(step1)" in gaps
    ops2 = [T.Op("x", 0, 10), T.Op("y", 100_010, 10)]
    spans2 = [T.Op("outer", 0, 200_000), T.Op("inner", 50_000, 20_000)]
    assert T.idle_gaps(ops2, spans2) == {
        "outer": pytest.approx(80_000e-9), "inner": pytest.approx(20_000e-9)}
