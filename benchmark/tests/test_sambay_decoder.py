"""The `sambay_decoder` family and the cell `phi4flash_s4096`: parameter
and operation counts against numbers worked by hand, the
configuration against the catalog's row (a copy under data/), the
cell's rehearsal through the harness's own `run_cell` — `correct` for the
sound program, not for the float8 control nor for the planted faults —
and the three readers this cell brings, BY NAME, on a synthetic ctx (no
scan kernel, no counter: None, never 0)."""
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.families import sambay_decoder as family
from benchmark.layer_metrics import (
    diff_attn_roofline_pct, selective_scan_ms_per_step,
    selective_scan_roofline_pct)
from benchmark.lib import cells, compare, peaks
from benchmark.lib import trace as T
from benchmark.lib.cells import Cell

CELL = "phi4flash_s4096"
READERS = {"selective_scan_ms_per_step": selective_scan_ms_per_step,
           "selective_scan_roofline_pct": selective_scan_roofline_pct,
           "diff_attn_roofline_pct": diff_attn_roofline_pct}
CATALOG_ROW = os.path.join(os.path.dirname(__file__), "data",
                           "phi4_mini_flash_reasoning.catalog_row.json")
CAUSAL_PAIRS = 4096 * 4097 // 2                           # 8,390,656
WINDOW_PAIRS = sum(min(r + 1, 512) for r in range(4096))  # 1,966,336


def _cell():
    c = Cell(CELL)
    return c, family.sizes(c.config), family.traffic(c.traffic)


def test_parameters_by_hand():
    """Mamba 119,895,040, attention 98,314,624, GMU 104,867,840, cross
    91,761,024 a layer; the tied slice 64,020,480 and the final
    LayerNorm: 577,178,752 trained parameters, 6.45 GiB live at 12
    bytes, 8.60 GiB at 16."""
    _, sz, _ = _cell()
    count = {n: int(np.prod(s))
             for n, s, _, _ in family.reference.param_specs(sz)}

    def layer(i):
        return sum(v for n, v in count.items()
                   if n.startswith(f"layer_{i}_"))
    assert [layer(i) for i in range(5)] == [
        98_314_624, 119_895_040, 98_314_624, 104_867_840, 91_761_024]
    assert count["embed_tokens.w_0"] == 25008 * 2560
    total = family.trained_parameters(sz)
    assert total == 577_178_752
    assert total * 12 / 2 ** 30 == pytest.approx(6.45, abs=0.005)
    assert total * 16 / 2 ** 30 == pytest.approx(8.60, abs=0.005)


def test_step_flops_by_hand():
    """Matrices of 512,983,040 parameters and the 64 M head, 6 x 4,096
    tokens each (14.18 TFLOP); differential attention 0.288 TFLOP
    forward (two causal sites of 8.39 M pairs, a window site of 1.97 M,
    40 half-heads x (64 + 128)), 0.86 with the backward: 15.04 a
    step."""
    _, sz, tr = _cell()
    mp = family.matmul_params(sz)
    dense = 2 * mp["attention"] + mp["mamba"] + mp["gmu"] + mp["cross"] \
        + 5 * mp["mlp"]
    assert dense == 512_983_040 and mp["head"] == 64_020_480
    f = family.flops_per_step(sz, tr)
    assert f["dense_step"] == 6 * 4096 * (dense + mp["head"])
    pairs = family.attention_pairs_per_step(sz, tr)
    assert pairs == 2 * CAUSAL_PAIRS + WINDOW_PAIRS
    assert f["attention_step"] == 3 * 2 * 40 * pairs * 192
    assert f["attention_step"] / 3e12 == pytest.approx(0.288, abs=0.001)
    assert f["step"] / 1e12 == pytest.approx(15.04, abs=0.005)


def test_counts_follow_the_programs_counters():
    _, sz, tr = _cell()
    uniform = family.flops_per_step(sz, tr)
    least = family.selective_scan_roofline_seconds_per_step(
        sz, tr, peaks.peaks("TPU v5 lite"))
    sz["_window_attn_pairs"] = np.asarray([WINDOW_PAIRS // 2], np.int64)
    sz["_selective_scan_tokens"] = np.asarray([2048], np.int64)
    half = family.flops_per_step(sz, tr)
    assert uniform["attention_step"] - half["attention_step"] == \
        pytest.approx(3 * 2 * 40 * 192 * WINDOW_PAIRS // 2)
    assert half["dense_step"] == uniform["dense_step"]
    assert family.selective_scan_roofline_seconds_per_step(
        sz, tr, peaks.peaks("TPU v5 lite")) == pytest.approx(least / 2)
    # x bf16, dt f32, y bf16 forward; x, dt, dy read and dx, d dt
    # written backward; B and C in and out at 16 a token
    assert family.selective_scan_bytes_per_token(sz) == \
        5120 * (8 + 10 + 6) + 3 * 64


def test_expected_routing_and_classifier():
    _, sz, tr = _cell()
    assert family.expected_routing(sz, tr) == {
        "fused_adam": "custom", "flash_attention": "custom",
        "selective_scan": "custom"}
    assert family.expected_routing(sz, tr, rehearsal=True) == {}
    for head, want in (
            ("%flash_attention_window_fwd.3", "flash_attention_window"),
            ("%flash_attention_fwd.2", "flash_attention"),
            ("%selective_scan_fwd.1", "selective_scan"),
            ("%selective_scan_bwd.4", "selective_scan"),
            ("%fused_adam.9", "fused_adam"),
            ("%mamba2_ssd_fwd.1", None)):
        assert family.classify_kernel(
            (), (), head + " = bf16[8]{0} custom-call()") == want


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    c, sz, tr = _cell()
    with open(CATALOG_ROW) as f:      # the published row, copied whole
        row = json.load(f)
    assert row["name"] == "Phi-4-mini-flash-reasoning"
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["configs"]
                 if e["name"] == "phi4_mini_flash_reasoning"][0]
    assert entry["source"] == c.config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"]) == [
        "layer_types", "num_hidden_layers", "vocab_size"]
    for key, published in row["config"].items():
        if key in c.config["reduced"]:
            cut = c.config["reduced"][key]
            assert cut["run"] == c.config[key] != published, key
            assert cut["published"] == published, key
        else:
            assert c.config[key] == published, key
    assert sz["layers"] == "SMFGX" and sz["first_layer"] == 15
    assert c.config["deployment"]["layers_held"] == [15, 16, 17, 18, 19]
    assert (sz["hidden_size"], sz["head_dim"], sz["intermediate_size"],
            sz["sliding_window"]) == (2560, 64, 10240, 512)
    assert (sz["num_attention_heads"], sz["num_key_value_heads"]) == (40, 20)
    assert family.reference.mamba_widths(sz) == (5120, 16, 160)
    assert sz["vocab_held"] * 8 == 200064
    assert tr["batch"] * tr["seq_len"] == 4096 and tr["pool"] == 8
    assert c.row["chips"] == 1


def _kernel(head, dur_ns):
    text = (f"%{head} = bf16[1,4096,5120]{{2,1,0}} custom-call(), "
            f"custom_call_target=\"tpu_custom_call\"")
    return T.Op(text, 0, dur_ns)


def _ctx(counters=True):
    _, sz, tr = _cell()
    if counters:
        sz.update(_selective_scan_tokens=np.asarray([4096], np.int64),
                  _window_attn_pairs=np.asarray([WINDOW_PAIRS], np.int64))
    ms = 1_000_000
    return {"steps": 2, "chips": 1, "family": family, "sizes": sz,
            "traffic": tr, "peaks": peaks.peaks("TPU v5 lite"),
            "trace": {"n_devices": 1, "by_category_s": {}, "kernels": {
                "selective_scan": [_kernel("selective_scan_fwd.1", 3 * ms),
                                   _kernel("selective_scan_bwd.1", 7 * ms)],
                "flash_attention": [_kernel("flash_attention_fwd.1",
                                            4 * ms)],
                "flash_attention_window": [
                    _kernel("flash_attention_window_fwd.1", 2 * ms)]}}}


def test_readers_on_a_synthetic_ctx():
    ctx = _ctx()
    assert READERS["selective_scan_ms_per_step"].read(ctx) == \
        pytest.approx(5.0)
    least = family.selective_scan_roofline_seconds_per_step(
        ctx["sizes"], ctx["traffic"], ctx["peaks"])
    assert READERS["selective_scan_roofline_pct"].read(ctx) == \
        pytest.approx(100 * least / 5e-3)
    flops = 3 * 2 * 40 * 192 * (2 * CAUSAL_PAIRS + WINDOW_PAIRS)
    pct = READERS["diff_attn_roofline_pct"].read(ctx)
    assert pct == pytest.approx(100 * flops / 197e12 / 3e-3)
    for name in READERS:
        assert cells.layer_metric_reader(name) is READERS[name].read


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_to_read_is_none_not_zero(name):
    ctx = _ctx()
    ctx["trace"]["kernels"] = {"fused_adam": [_kernel("fused_adam.7", 10)]}
    assert READERS[name].read(ctx) is None
    if name != "selective_scan_ms_per_step":
        assert READERS[name].read(_ctx(counters=False)) is None


# ----------------------------------------------- correct, control, fault

def _drive(hook=None, seed=13):
    out, err = io.StringIO(), io.StringIO()
    res = bench_run.run_cell(Cell(CELL), seed, 0.3, 0, True,
                             time.perf_counter(), session_hook=hook,
                             out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def _lambda_init_at_the_stages_own_index(sess):
    """A program whose lambda_init reads the layer's index in the stage
    (0-4) in place of its published one (15-19)."""
    for op in sess.main.global_block().ops:
        if op.type.startswith("differential_attention"):
            scope = op.attr("op_namescope", "")
            i = int(scope.split("/")[0].split("_")[1])
            op.set_attr("lambda_init", 0.8 - 0.6 * np.exp(-0.3 * i))


def _widen_the_window_by_one(sess):
    """A program whose band admits one key more than the window."""
    for op in sess.main.global_block().ops:
        if op.type in ("fused_attention", "fused_attention_grad") \
                and op.attr("window", 0):
            op.set_attr("window", op.attr("window") + 1)


def test_rehearsal_is_correct():
    res = _drive()
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("hook", [_lambda_init_at_the_stages_own_index,
                                  _widen_the_window_by_one],
                         ids=lambda h: h.__name__.strip("_"))
def test_fault_planted_in_the_program_is_not_correct(hook):
    res = _drive(hook=hook)
    assert res["correct"] is False
    over = [k for k, c in res["compared"].items() if c["value"] > c["limit"]]
    assert set(over) & set(compare.NUMBERS), res["compared"]


@pytest.mark.parametrize("seed", [5, 6])
def test_control_and_faults_are_not_correct(seed):
    """The float8 control and every fault of `FAULTS` over a rehearsal
    limit (`FAULTS_UNSEEN` read within the program's own rounding)."""
    c = Cell(CELL)
    sz, tr = family.sizes(c.config, True), family.traffic(c.traffic, True)
    pool = family.make_pool(sz, tr, seed)
    ref = family.run_reference(sz, tr, pool, seed, 3)
    ctl = family.run_reference(sz, tr, pool, seed, 3, precision="fp8")
    values, _ = compare.gaps(ctl, ref)
    assert not compare.judge(values, c.limits_for(True))[1], values
    for fault in family.reference.FAULTS:
        bad = family.run_reference(sz, tr, pool, seed, 3, fault=fault)
        values, _ = compare.gaps(bad, ref)
        assert not compare.judge(values, c.limits_for(True))[1], \
            (fault, values)
