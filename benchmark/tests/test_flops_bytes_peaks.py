"""The FLOP and byte functions on the three cells' shapes against the
numbers of ISSUE 25, and the peaks table."""
import json
import os

import pytest

from benchmark.families import transformer_encdec as family
from benchmark.lib import cells, peaks


def _cell(config, traffic):
    sz = family.sizes(json.load(open(os.path.join(
        cells.BENCH, "configs", config + ".json"))))
    tr = json.load(open(os.path.join(cells.BENCH, "traffic",
                                     traffic + ".json")))
    return sz, tr


@pytest.mark.parametrize("config,traffic,tflop,attention_share", [
    ("transformer_base", "fixed_b96_s128", 4.6, 0.0315),
    ("transformer_big", "fixed_b64_s128", 10.5, 0.0185),
    ("transformer_base", "fixed_b4_s4096", 12.1, 0.51),
])
def test_step_flops(config, traffic, tflop, attention_share):
    sz, tr = _cell(config, traffic)
    f = family.flops_per_step(sz, tr)
    assert f["step"] / 1e12 == pytest.approx(tflop, abs=0.05)
    assert f["step"] == f["dense_step"] + f["attention_step"]
    assert f["attention_step"] / f["step"] == pytest.approx(
        attention_share, abs=0.005)


def test_attention_flops_by_hand():
    sz, tr = _cell("transformer_base", "fixed_b4_s4096")
    one = 4 * 4 * 4096 * 4096 * 512        # 4*B*Sq*Sk*d_model
    assert family.attention_flops_forward(sz, tr) == 6 * one + 6 * one // 2 \
        + 6 * one


@pytest.mark.parametrize("config,params_m,routed_m", [
    ("transformer_base", 93.3, 93.2), ("transformer_big", 274.7, 274.5)])
def test_parameters_and_adam_bytes(config, params_m, routed_m):
    sz, _ = _cell(config, "fixed_b96_s128")
    specs = family.reference.param_specs(sz)
    total = sum(int(__import__("numpy").prod(s)) for _, s, _, _ in specs)
    assert total / 1e6 == pytest.approx(params_m, abs=0.05)
    assert family.adam_routed_elements(sz) / 1e6 == pytest.approx(
        routed_m, abs=0.05)
    assert family.adam_routed_bytes_per_step(sz) == \
        28 * family.adam_routed_elements(sz)
    assert len(specs) == 255


def test_expected_routing():
    sz, tr = _cell("transformer_base", "fixed_b96_s128")
    assert family.expected_routing(sz, tr)["flash_attention"] == "lowered"
    sz, tr = _cell("transformer_base", "fixed_b4_s4096")
    assert family.expected_routing(sz, tr) == {
        "fused_adam": "custom", "flash_attention": "custom"}


def test_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks recorded"):
        peaks.peaks("TPU v7 imaginary")


def test_every_benchmark_entry_has_its_files():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = cells.Cell(w["name"])
        assert cell.family.KIND == "train"
        for m in cell.per_layer():
            assert callable(cells.layer_metric_reader(m["name"]))
