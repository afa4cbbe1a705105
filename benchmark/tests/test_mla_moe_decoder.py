"""The `mla_moe_decoder` family and the cell `kanana2_s4096`: operation
and byte counts against numbers worked by hand (ISSUE 28), the cell's
rehearsal through the harness's own `run_cell` — `correct` for the sound
program, not for the float8 control nor for a fault planted in the
program (the chosen experts' weights left un-normalised) — and the five
readers this cell brings, on synthetic events, on the traces recorded at
PR 25 and PR 26 (no expert kernels, no counter: None, never 0)."""
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.families import mla_moe_decoder as family
from benchmark.layer_metrics import (
    mla_flash_roofline_pct, moe_expert_load_max_over_mean,
    moe_gmm_ms_per_step, moe_gmm_roofline_pct, moe_held_rows_per_token)
from benchmark.lib import cells, compare, peaks
from benchmark.lib import trace as T
from benchmark.lib.cells import Cell

from test_layer_metrics_named import _ctx_of

CELL = "kanana2_s4096"
READERS = [mla_flash_roofline_pct, moe_gmm_ms_per_step,
           moe_gmm_roofline_pct, moe_held_rows_per_token,
           moe_expert_load_max_over_mean]


def _cell():
    c = Cell(CELL)
    return c, family.sizes(c.config), family.traffic(c.traffic)


# ------------------------------------------------- operations and bytes

def test_parameters_by_hand():
    _, sz, _ = _cell()
    specs = family.reference.param_specs(sz)
    count = {n: int(np.prod(s)) for n, s, _, _ in specs}
    trained = sum(v for n, v in count.items()
                  if not family.reference.is_buffer(n))
    attn = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert attn == 26_345_472 == family.matmul_params(sz)["attention"]
    assert attn + 512 == 26_345_984         # ISSUE 28's, with the kv norm
    layer0 = attn + 3 * 2048 * 6144 + 2048 + 512 + 2048      # + norms
    moe = (attn + 128 * 2048 + 3 * 2048 * 1536 + 16 * 3 * 2048 * 768
           + 2048 + 512 + 2048)
    assert layer0 / 1e6 == pytest.approx(64.10, abs=0.01)
    assert moe / 1e6 == pytest.approx(111.55, abs=0.01)
    assert trained == layer0 + 4 * moe + 2 * 16032 * 2048 + 2048
    assert trained / 1e6 == pytest.approx(575.96, abs=0.01)
    # three stacked leaves a MoE layer, not 48; one buffer a MoE layer
    assert count["layer_3_experts_gate.w_0"] == 16 * 2048 * 768
    assert sum(family.reference.is_buffer(n) for n in count) == 4
    assert len(specs) == 1 + 10 + 4 * 15 + 2
    assert family.adam_routed_bytes_per_step(sz) == \
        28 * family.adam_routed_elements(sz)
    # every matrix is over the kernel's floor; the 16 norms are not
    assert family.adam_routed_elements(sz) == trained - (
        5 * (2048 + 512 + 2048) + 2048)


def test_step_flops_by_hand():
    _, sz, tr = _cell()
    f = family.flops_per_step(sz, tr)
    one_attention = 2 * 1 * 32 * 4096 * 4096 * (192 + 128) // 2
    assert family.attention_flops_forward(sz, tr) == 5 * one_attention
    assert f["attention_step"] == 15 * one_attention
    assert f["attention_step"] / 1e12 == pytest.approx(2.58, abs=0.01)
    # uniform routing: 6 * 16 / 128 = 0.75 rows a token a MoE layer
    assert family.routed_rows_per_step(sz, tr) == 4 * 4096 * 0.75
    assert f["routed_step"] == 3 * 2 * 12288 * 3 * 2048 * 768
    per_token = (5 * 26_345_472 + 3 * 2048 * 6144
                 + 4 * (128 * 2048 + 3 * 2048 * 1536) + 2048 * 16032)
    assert f["dense_step"] == 3 * 2 * 4096 * per_token
    assert f["step"] == f["dense_step"] + f["routed_step"] \
        + f["attention_step"]
    assert f["step"] / 1e12 == pytest.approx(8.8, abs=0.1)
    assert (f["dense_step"] + f["routed_step"]) / 1e12 == pytest.approx(
        6.3, abs=0.1)


def test_routed_flops_follow_the_programs_count():
    _, sz, tr = _cell()
    uniform = family.flops_per_step(sz, tr)["routed_step"]
    # the counter after three proof steps: 3 * 4096 tokens a layer
    sz[family._LOAD_KEY] = np.full((4, 16), 3 * 150, np.int64)
    assert family.routed_rows_per_step(sz, tr) == 4 * 16 * 150
    assert family.flops_per_step(sz, tr)["routed_step"] == pytest.approx(
        uniform * (16 * 150) / (4096 * 0.75))


def test_grouped_matmul_bytes_by_hand():
    _, sz, tr = _cell()
    weights = 4 * 16 * 2048 * 768 * (6 * 2 + 3 * 4)
    rows = 9 * 12288 * (2048 + 768) * 2
    assert family.grouped_matmul_bytes_per_step(sz, tr) == weights + rows
    # the kernels are bound by the experts' matrices, not by the MXU:
    # the least time the bytes need exceeds the least the FLOPs need
    p = peaks.peaks("TPU v5 lite")
    flops = family.flops_per_step(sz, tr)["routed_step"]
    assert (weights + rows) / p["bytes_per_s"] > flops / p["flops_per_s"]


def test_expected_routing_and_classifier():
    _, sz, tr = _cell()
    assert family.expected_routing(sz, tr) == {
        "fused_adam": "custom", "flash_attention": "custom",
        "moe_grouped_matmul": "custom"}
    assert family.expected_routing(sz, tr, rehearsal=True) == {}
    for head, kernel in (("%moe_grouped_matmul_dw.7", "moe_grouped_matmul"),
                         ("%flash_attention_dkv", "flash_attention"),
                         ("%fused_adam.12", "fused_adam"),
                         ("%fused_sgd", None)):
        assert family.classify_kernel([], [], head + " = f32[8] x") == kernel


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    c, sz, tr = _cell()
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["configs"]
                 if e["name"] == "kanana2_30b_a3b"][0]
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"])
    for key, cut in c.config["reduced"].items():
        assert c.config[key] == cut["run"] != cut["published"]
    assert sz["router_experts"] == 128 and sz["experts_held"] == 16
    assert sz["vocab_held"] * 8 == 128256
    assert c.config["deployment"]["chips_sharing_each_layer"] == 8
    assert tr["batch"] * tr["seq_len"] == 4096
    assert "eighth" in c.row["why"] and c.row["chips"] == 1


# ----------------------------------------------- correct, control, fault

def _drive(hook=None, seed=13):
    out, err = io.StringIO(), io.StringIO()
    res = bench_run.run_cell(Cell(CELL), seed, 0.3, 0, True,
                             time.perf_counter(), session_hook=hook,
                             out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def _leave_the_weights_unnormalised(sess):
    """The chosen experts' weights left un-normalised, in the program the
    window drives."""
    for op in sess.main.global_block().ops:
        if op.type in ("moe_router", "moe_router_grad"):
            op.set_attr("norm_topk_prob", False)


def test_rehearsal_is_correct():
    res = _drive()
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_planted_fault_is_not_correct():
    res = _drive(hook=_leave_the_weights_unnormalised)
    assert res["correct"] is False
    over = [k for k, c in res["compared"].items() if c["value"] > c["limit"]]
    assert set(over) & set(compare.NUMBERS), res["compared"]


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_control_is_not_correct(seed):
    c = Cell(CELL)
    sz, tr = family.sizes(c.config, True), family.traffic(c.traffic, True)
    pool = family.make_pool(sz, tr, seed)
    ref = family.run_reference(sz, tr, pool, seed, 3)
    ctl = family.run_reference(sz, tr, pool, seed, 3, precision="fp8")
    values, _ = compare.gaps(ctl, ref)
    assert not compare.judge(values, c.limits_for(True))[1], values
    for fault in ("half_positions", "unnormalised_topk"):
        bad = family.run_reference(sz, tr, pool, seed, 3, fault=fault)
        values, _ = compare.gaps(bad, ref)
        assert not compare.judge(values, c.limits_for(True))[1], values
    same, _ = compare.gaps(ref, ref)
    assert compare.judge(same, c.limits_for(True))[1]


# ------------------------------------------------------------ the readers

def _kernel(head, dur_ns):
    return T.Op(f"%{head} = bf16[26624,768]{{1,0}} custom-call(bf16[26624,"
                f"2048]{{1,0}} %a), custom_call_target=\"tpu_custom_call\"",
                0, dur_ns)


def _ctx(load=None):
    _, sz, tr = _cell()
    if load is not None:
        sz[family._LOAD_KEY] = load
    gmm = [_kernel(f"moe_grouped_matmul_{k}.{i}", 250_000)
           for k in ("fwd", "dx", "dw") for i in range(12)] * 2
    flash = [_kernel(f"flash_attention_{k}.{i}", ns)
             for k, ns in (("fwd", 3_000_000), ("dq", 4_000_000),
                           ("dkv", 5_000_000)) for i in range(5)] * 2
    return {"steps": 2, "chips": 1, "family": family, "sizes": sz,
            "traffic": tr, "peaks": peaks.peaks("TPU v5 lite"),
            "trace": {"n_devices": 1, "kernels": {
                "moe_grouped_matmul": gmm, "flash_attention": flash}}}


def test_readers_on_a_synthetic_ctx():
    load = np.tile(np.arange(16) * 10 + 501, (4, 1)).astype(np.int64)
    ctx = _ctx(load)
    assert moe_gmm_ms_per_step.read(ctx) == pytest.approx(9.0)
    rows = load.sum() / 3                 # a step, all layers
    flops = 3 * 2 * rows * 3 * 2048 * 768
    assert moe_gmm_roofline_pct.read(ctx) == pytest.approx(
        100 * flops / 197e12 / 9.0e-3)
    assert moe_held_rows_per_token.read(ctx) == pytest.approx(
        load[0].sum() / (3 * 4096))
    assert moe_expert_load_max_over_mean.read(ctx) == pytest.approx(
        651 / load[0].mean())
    attention = 15 * (2 * 32 * 4096 * 4096 * 320 // 2)
    assert mla_flash_roofline_pct.read(ctx) == pytest.approx(
        100 * attention / 197e12 / 60e-3)
    assert all(r.read(ctx) <= 100 for r in READERS[:1])


@pytest.mark.parametrize("reader", READERS,
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_nothing_to_read_is_none_not_zero(reader):
    # a program with no expert kernels, no flash names and no counter
    ctx = _ctx()
    ctx["trace"]["kernels"] = {"flash_attention": [_kernel("step1.7", 10)]}
    assert reader.read(ctx) is None
    # a counter that never counted
    ctx = _ctx(np.zeros((4, 16), np.int64))
    if reader in (moe_held_rows_per_token, moe_expert_load_max_over_mean,
                  moe_gmm_roofline_pct):
        assert reader.read(ctx) is None


@pytest.mark.parametrize("data", ["tbase_s4096_two_steps.events.json.gz",
                                  "tbase_s4096_two_steps_named.events.json.gz"])
@pytest.mark.parametrize("reader", READERS[1:],
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_the_recorded_transformer_traces_give_nothing(reader, data):
    """Neither recorded trace has an expert kernel, and the Transformer
    family reads no counter."""
    from benchmark.families import transformer_encdec
    recorded, _, _ = _ctx_of(data)
    ctx = dict(_ctx(), family=transformer_encdec, **recorded)
    assert ctx["trace"]["kernels"]["flash_attention"]
    assert reader.read(ctx) is None


def test_mla_flash_reader_needs_the_programs_names():
    recorded, _, _ = _ctx_of("tbase_s4096_two_steps.events.json.gz")
    assert mla_flash_roofline_pct.read(dict(_ctx(), **recorded)) is None
    named, _, _ = _ctx_of("tbase_s4096_two_steps_named.events.json.gz")
    assert mla_flash_roofline_pct.read(dict(_ctx(), **named)) > 0


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "kanana2_s4096_two_steps.events.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no trace of the cell was recorded")
def test_readers_on_the_recorded_trace():
    """Steps 2 and 3 of 4 traced through the benchmark's own Session at
    the cell's size on 1 x TPU v5 lite (my chip run, PR 28), with the
    counter as read after the proof steps; figures by hand with plain
    loops."""
    import collections
    import gzip
    with gzip.open(RECORDED, "rt") as f:
        raw = json.load(f)
    (_, events), = raw["devices"].items()
    ops = [T.Op(*e) for e in events]
    kernels = collections.defaultdict(list)
    for op in ops:
        if T.is_pallas(op):
            kernels[family.classify_kernel([], [], op.name)].append(op)
    assert set(kernels) == {"fused_adam", "flash_attention",
                            "moe_grouped_matmul"}
    heads = collections.Counter(
        op.name[1:op.name.index(" =")].rpartition(".")[0] or op.name
        for op in kernels["moe_grouped_matmul"])
    assert heads == {"moe_grouped_matmul_fwd": 24,
                     "moe_grouped_matmul_dx": 24,
                     "moe_grouped_matmul_dw": 24}
    assert len(kernels["flash_attention"]) == 30       # 5 + 5 + 5 a step
    load = np.asarray(raw["moe_expert_load"], np.int64)
    assert load.shape == (4, 16)
    ctx = dict(_ctx(load), steps=2,
               trace={"n_devices": 1, "kernels": dict(kernels)})
    gmm_ms = sum(o.dur_ns for o in kernels["moe_grouped_matmul"]) / 2e6
    assert moe_gmm_ms_per_step.read(ctx) == pytest.approx(gmm_ms)
    assert 3 < gmm_ms < 15
    rows = load.sum() / 3
    assert moe_gmm_roofline_pct.read(ctx) == pytest.approx(
        100 * 18 * rows * 2048 * 768 / 197e12 / (gmm_ms / 1e3))
    assert 5 < moe_gmm_roofline_pct.read(ctx) < 100
    assert 0.6 < moe_held_rows_per_token.read(ctx) < 0.9
    assert 1.0 <= moe_expert_load_max_over_mean.read(ctx) < 6
    assert 10 < mla_flash_roofline_pct.read(ctx) < 100


def test_every_new_entry_has_its_reader():
    for m in Cell(CELL).per_layer():
        assert callable(cells.layer_metric_reader(m["name"]))
    names = {m["name"] for m in Cell(CELL).per_layer()}
    assert {r.__name__.rsplit(".", 1)[1] for r in READERS} <= names
    assert "flash_attn_roofline_pct" not in names
