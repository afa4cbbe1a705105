"""The `swa_gqa_moe_decoder` family and the cell `mellum2_s8192`:
parameter and operation counts against numbers worked by hand (ISSUE 41),
the configuration against the catalog's row (a copy under data/), the
cell's rehearsal through the harness's own `run_cell` — `correct` for the
sound program, not for the float8 control nor for the planted faults —
and the two readers this cell brings, BY NAME, on synthetic events and on
the traces recorded of the other families (no window kernel, no counter:
None, never 0)."""
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.families import swa_gqa_moe_decoder as family
from benchmark.layer_metrics import (
    _swa, swa_flash_ms_per_step, swa_flash_roofline_pct)
from benchmark.lib import cells, compare, peaks
from benchmark.lib import trace as T
from benchmark.lib.cells import Cell

from test_layer_metrics_named import _ctx_of

CELL = "mellum2_s8192"
READERS = {"swa_flash_ms_per_step": swa_flash_ms_per_step,
           "swa_flash_roofline_pct": swa_flash_roofline_pct}
CATALOG_ROW = os.path.join(os.path.dirname(__file__), "data",
                           "mellum2_12b_a2p5b.catalog_row.json")
WINDOW_PAIRS = 7_864_832          # S = 8,192, a window of 1,024
CAUSAL_PAIRS = 8192 * 8193 // 2   # 33,558,528


def _cell():
    c = Cell(CELL)
    return c, family.sizes(c.config), family.traffic(c.traffic)


# ------------------------------------------------- operations and bytes

def test_parameters_by_hand():
    """A layer: attention 21,233,664 + router 147,456 + norms 4,608 + 8
    experts of 6,193,152 = 70,930,944; four of them, the untied
    embedding and head 2 x 12,288 x 2,304 and the final norm: 340,349,184
    trained parameters, 3.80 GiB live at 12 bytes, 5.07 GiB at 16."""
    _, sz, _ = _cell()
    count = {n: int(np.prod(s))
             for n, s, _, _ in family.reference.param_specs(sz)}
    mp = family.matmul_params(sz)
    assert mp["attention"] == 21_233_664
    assert mp["router"] == 147_456 and mp["routed_expert"] == 6_193_152
    layer = sum(v for n, v in count.items() if n.startswith("layer_0_"))
    assert layer == 70_930_944
    assert count["embed_tokens.w_0"] == count["lm_head.w_0"] == 12288 * 2304
    total = family.trained_parameters(sz)
    assert total == 4 * 70_930_944 + 56_623_104 + 2304 == 340_349_184
    assert total * 12 / 2 ** 30 == pytest.approx(3.80, abs=0.005)
    assert total * 16 / 2 ** 30 == pytest.approx(5.07, abs=0.005)
    assert not any(family.reference.is_buffer(n) for n in count)
    assert family.adam_routed_elements(sz) <= total


def test_step_flops_by_hand():
    """Attention 2.81 of the step's ~9.6 TFLOP (29%) beside 6.81 TFLOP of
    projections, routers, experts and head (ISSUE 41's 6.78 leaves out
    the four routers' 0.03); a kernel that worked the causal square
    on the window layers would need 6.60 TFLOP of attention, so the band
    saves 28% of that step's work."""
    _, sz, tr = _cell()
    f = family.flops_per_step(sz, tr)
    assert family.window_pairs_per_step(sz, tr) == 3 * WINDOW_PAIRS
    assert family.causal_pairs_per_step(sz, tr) == CAUSAL_PAIRS
    window = 3 * 2 * 32 * 3 * WINDOW_PAIRS * 256
    full = 3 * 2 * 32 * CAUSAL_PAIRS * 256
    assert f["window_attention_step"] == window
    assert f["full_attention_step"] == full
    assert f["attention_step"] / 1e12 == pytest.approx(2.81, abs=0.005)
    # uniform routing: each held expert sees 8,192 * 8 / 64 = 1,024 rows
    rows = family.routed_rows_per_step(sz, tr)
    assert rows == 4 * 8 * 1024
    per_token = 4 * (21_233_664 + 147_456) + 12288 * 2304
    other = 3 * (2 * 8192 * per_token + 2 * rows * 6_193_152)
    assert f["dense_step"] + f["routed_step"] == pytest.approx(other)
    assert other / 1e12 == pytest.approx(6.81, abs=0.005)
    assert f["step"] / 1e12 == pytest.approx(9.62, abs=0.01)
    assert f["attention_step"] / f["step"] == pytest.approx(0.29, abs=0.005)
    masked = 3 * 2 * 32 * 4 * CAUSAL_PAIRS * 256
    assert masked / 1e12 == pytest.approx(6.60, abs=0.005)
    assert (masked - f["attention_step"]) / (other + masked) \
        == pytest.approx(0.28, abs=0.005)
    assert WINDOW_PAIRS / CAUSAL_PAIRS == pytest.approx(0.234, abs=0.0005)


def test_flops_follow_the_programs_counters():
    _, sz, tr = _cell()
    uniform = family.flops_per_step(sz, tr)
    sz[family._LOAD_KEY] = np.full((4, 8), 3 * 512, np.int64)   # half
    sz[family._PAIRS_KEY] = np.full((3,), WINDOW_PAIRS // 2, np.int64)
    half = family.flops_per_step(sz, tr)
    assert half["routed_step"] == pytest.approx(uniform["routed_step"] / 2)
    assert half["window_attention_step"] == pytest.approx(
        uniform["window_attention_step"] / 2)
    assert half["full_attention_step"] == uniform["full_attention_step"]
    assert half["dense_step"] == uniform["dense_step"]
    assert family.window_pairs(sz).tolist() == [WINDOW_PAIRS // 2] * 3


def test_expected_routing_and_classifier():
    """The window layers' kernels are booked apart from the full layer's
    (the window hint is tried first: its names hold `flash_attention`
    too)."""
    _, sz, tr = _cell()
    assert family.expected_routing(sz, tr) == {
        "fused_adam": "custom", "flash_attention": "custom",
        "moe_grouped_matmul": "custom"}
    assert family.expected_routing(sz, tr, rehearsal=True) == {}
    for head, want in (
            ("%flash_attention_window_fwd.3", "flash_attention_window"),
            ("%flash_attention_window_bwd.1", "flash_attention_window"),
            ("%flash_attention_window_dq.1", "flash_attention_window"),
            ("%flash_attention_fwd.2", "flash_attention"),
            ("%flash_attention_dkv.2", "flash_attention"),
            ("%moe_grouped_matmul_dw.5", "moe_grouped_matmul"),
            ("%moe_combine.1", "moe_combine"),
            ("%fused_adam.9", "fused_adam"),
            ("%something_else.1", None)):
        assert family.classify_kernel(
            (), (), head + " = bf16[8]{0} custom-call()") == want


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    c, sz, tr = _cell()
    with open(CATALOG_ROW) as f:      # the published row, copied whole
        row = json.load(f)
    assert row["name"] == "Mellum2-12B-A2.5B-Instruct"
    assert len(row["config"]) == 23
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["configs"]
                 if e["name"] == "mellum2_12b_a2p5b"][0]
    assert entry["source"] == c.config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"]) == [
        "layer_types", "mlp_layer_types", "num_experts",
        "num_hidden_layers", "vocab_size"]
    for key, published in row["config"].items():
        if key in c.config["reduced"]:
            cut = c.config["reduced"][key]
            assert cut["run"] == c.config[key] != published, key
            if not key.endswith("layer_types"):   # described in words
                assert cut["published"] == published, key
        else:
            assert c.config[key] == published, key
    # the run's layers are published layers 0-3, one whole period
    assert c.config["layer_types"] == row["config"]["layer_types"][:4]
    assert row["config"]["layer_types"].count("sliding_attention") == 21
    assert sz["layers"] == "SSSF" and sz["sliding_window"] == 1024
    assert sz["router_experts"] == 64 and sz["experts_held"] == 8
    assert sz["vocab_held"] * 8 == 98304 and sz["head_dim"] == 128
    assert not sz["tie_word_embeddings"] and not sz["use_qk_norm"]
    assert sz["yarn"]["attention_factor"] == 1.2772588722239782
    assert c.config["deployment"]["chips_sharing_each_layer"] == 8
    assert tr["batch"] * tr["seq_len"] == 8192 and tr["pool"] == 8
    assert tr["fetch"] == "every_step" and c.row["chips"] == 1
    for said in ("7.86M", "23.4%", "33.56M", "2.81", "9.6 TFLOP", "29%",
                 "6.60", "-28%", "1,024 rows"):
        assert said in c.row["why"], said
    cfg = family.model_config(sz)
    assert cfg.mixers == ["swa", "swa", "swa", "attn"]
    assert cfg.rms_norm_eps == 1e-6 and cfg.num_key_value_heads == 4


# ----------------------------------------------- correct, control, fault

def _drive(hook=None, seed=13):
    out, err = io.StringIO(), io.StringIO()
    res = bench_run.run_cell(Cell(CELL), seed, 0.3, 0, True,
                             time.perf_counter(), session_hook=hook,
                             out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def _leave_the_weights_unnormalised(sess):
    for op in sess.main.global_block().ops:
        if op.type in ("moe_router", "moe_router_grad"):
            op.set_attr("norm_topk_prob", False)


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


@pytest.mark.parametrize("hook", [_leave_the_weights_unnormalised,
                                  _widen_the_window_by_one],
                         ids=lambda h: h.__name__.strip("_"))
def test_fault_planted_in_the_program_is_not_correct(hook):
    res = _drive(hook=hook)
    assert res["correct"] is False
    over = [k for k, c in res["compared"].items() if c["value"] > c["limit"]]
    assert set(over) & set(compare.NUMBERS), res["compared"]


@pytest.mark.parametrize("seed", [5, 6])
def test_control_and_faults_are_not_correct(seed):
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


def test_the_shipped_limits_separate_the_chips_readings():
    lim = Cell(CELL).limits
    for number in ("grad_gap", "delta_gap", "grad_dir_gap"):
        r = lim["readings"][number]
        assert max(r["program_all"]) == pytest.approx(r["lower"])
        assert r["lower"] < lim[number], number
        assert lim[number] < min(r["caught_by_this_number_min"].values()), \
            number
    # the float8 control is over a limit on every seed it was read on
    ctl = lim["readings"]["grad_dir_gap"]["control"]
    assert all(v is None or v > lim["grad_dir_gap"] for v in ctl)


# ------------------------------------------------------------ the readers

def _kernel(head, dur_ns):
    return T.Op(f"%{head} = bf16[1,8192,4096]{{2,1,0}} custom-call(bf16[1,"
                f"8192,4096]{{2,1,0}} %a), custom_call_target="
                f"\"tpu_custom_call\"", 0, dur_ns)


def _ctx(pairs=None):
    _, sz, tr = _cell()
    if pairs is not None:
        sz[family._PAIRS_KEY] = pairs
    window = [_kernel(f"flash_attention_window_{k}.{i}", ns)
              for k, ns in (("fwd", 3_000_000), ("bwd", 5_000_000))
              for i in range(3)] * 2
    flash = [_kernel("flash_attention_fwd.1", 7_000_000),
             _kernel("flash_attention_dkv.1", 12_000_000)] * 2
    return {"steps": 2, "chips": 1, "family": family, "sizes": sz,
            "traffic": tr, "peaks": peaks.peaks("TPU v5 lite"),
            "trace": {"n_devices": 1, "by_category_s": {},
                      "kernels": {"flash_attention_window": window,
                                  "flash_attention": flash}}}


def test_readers_on_a_synthetic_ctx():
    """By name: each reader's module is the one its metric names."""
    ctx = _ctx(np.full((3,), WINDOW_PAIRS, np.int64))
    ms = READERS["swa_flash_ms_per_step"].read(ctx)
    assert ms == pytest.approx(3 * (3 + 5))
    flops = 3 * 2 * 32 * 3 * WINDOW_PAIRS * 256
    assert _swa.window_flops_per_step(ctx["sizes"], np.full(
        (3,), WINDOW_PAIRS)) == flops
    pct = READERS["swa_flash_roofline_pct"].read(ctx)
    assert pct == pytest.approx(100 * flops / 197e12 / 24e-3)
    assert 0 < pct < 100
    half = _ctx(np.full((3,), WINDOW_PAIRS // 2, np.int64))
    assert READERS["swa_flash_roofline_pct"].read(half) == pytest.approx(
        pct / 2, rel=1e-6)


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_to_read_is_none_not_zero(name):
    reader = READERS[name]
    # a program with no window kernel
    ctx = _ctx(np.full((3,), WINDOW_PAIRS, np.int64))
    ctx["trace"]["kernels"] = {"fused_adam": [_kernel("fused_adam.7", 10)]}
    assert reader.read(ctx) is None
    # a counter that never counted, and a family that reads none
    for pairs in (np.zeros((3,), np.int64), None):
        if name == "swa_flash_roofline_pct":
            assert reader.read(_ctx(pairs)) is None


@pytest.mark.parametrize("data", ["tbase_s4096_two_steps_named.events.json.gz",
                                  "kanana2_s4096_two_steps.events.json.gz"])
@pytest.mark.parametrize("name", sorted(READERS))
def test_the_other_families_recorded_traces_give_nothing(name, data):
    """Neither recorded trace has a window kernel and neither family
    reads an admitted-pairs counter (the parent under this PR's
    benchmark files): None, never 0, and nothing raised."""
    import collections
    import gzip
    path = os.path.join(os.path.dirname(__file__), "data", data)
    if data.startswith("tbase"):
        from benchmark.families import transformer_encdec as other
        recorded, _, _ = _ctx_of(data)
        ctx = dict(_ctx(), family=other, **recorded)
    else:
        from benchmark.families import mla_moe_decoder as other
        with gzip.open(path, "rt") as f:
            (_, events), = json.load(f)["devices"].items()
        kernels = collections.defaultdict(list)
        for op in (T.Op(*e) for e in events):
            if T.is_pallas(op):
                kernels[other.classify_kernel([], [], op.name)].append(op)
        c = Cell("kanana2_s4096")
        ctx = dict(_ctx(), family=other, sizes=other.sizes(c.config),
                   traffic=other.traffic(c.traffic),
                   trace={"n_devices": 1, "by_category_s": {},
                          "kernels": dict(kernels)})
    assert ctx["trace"]["kernels"]["flash_attention"]
    assert READERS[name].read(ctx) is None


def test_every_new_entry_has_its_reader():
    """By NAME, not by position: a later PR appends."""
    cell = Cell(CELL)
    for m in cell.per_layer():
        assert callable(cells.layer_metric_reader(m["name"]))
    for name, module in READERS.items():
        assert cells.layer_metric_reader(name) is module.read
    names = {m["name"] for m in cell.per_layer()}
    assert set(READERS) <= names
    assert {"step_mfu_pct", "fused_adam_roofline_pct", "host_ms_per_step",
            "device_idle_pct", "xla_ops_ms_per_step"} <= names
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(new) == set(READERS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "items_per_s"
               and m["layer"] == "Pallas kernels"
               and m["source"] == "device_trace" for m in new.values())
    assert new["swa_flash_ms_per_step"]["unit"] == "ms"
    assert new["swa_flash_roofline_pct"]["unit"] == "%"
    assert [w for w in bench["workloads"] if w["name"] == CELL] == [cell.row]
    assert [c["name"] for c in bench["configs"]].count(
        "mellum2_12b_a2p5b") == 1
