"""The `conv_gqa_moe_decoder` family and the cell `lfm2_s8192`:
parameter, operation and byte counts against numbers worked by hand
(ISSUE 39), the configuration against the catalog's row (a copy under
data/), the cell's rehearsal through the harness's own `run_cell` —
`correct` for the sound program, not for the float8 control nor for the
planted faults — and the three readers this cell brings, on synthetic
events and on the traces recorded of the other families (no convolution
kernel, no counter: None, never 0)."""
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.families import conv_gqa_moe_decoder as family
from benchmark.layer_metrics import (
    gqa64_flash_roofline_pct, short_conv_ms_per_step,
    short_conv_roofline_pct)
from benchmark.lib import cells, compare, peaks
from benchmark.lib import trace as T
from benchmark.lib.cells import Cell

from test_layer_metrics_named import _ctx_of

CELL = "lfm2_s8192"
READERS = [short_conv_ms_per_step, short_conv_roofline_pct,
           gqa64_flash_roofline_pct]
NEW = {r.__name__.rsplit(".", 1)[1] for r in READERS}
# the faults the REHEARSAL's limits separate on every seed (limits file,
# "rehearsal"): all but "bias_in_weights", which a zero bias cannot show
# (tests/test_decoder_lm.py reads it with a nonzero one); the chip's
# readings at 8,192 tokens are in the limits file's "rule"
FAULTS = tuple(f for f in family.reference.FAULTS if f != "bias_in_weights")
CATALOG_ROW = os.path.join(os.path.dirname(__file__), "data",
                           "lfm2_24b_a2b.catalog_row.json")


def _cell():
    c = Cell(CELL)
    return c, family.sizes(c.config), family.traffic(c.traffic)


# ------------------------------------------------- operations and bytes

def test_parameters_by_hand():
    _, sz, _ = _cell()
    specs = family.reference.param_specs(sz)
    count = {n: int(np.prod(s)) for n, s, _, _ in specs}
    mp = family.matmul_params(sz)
    assert mp["conv"] == 2048 * 6144 + 2048 * 2048
    operator = mp["conv"] + 2048 * 3                       # + the taps
    assert operator / 1e6 == pytest.approx(16.78, abs=0.01)
    assert mp["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert mp["attention"] / 1e6 == pytest.approx(10.49, abs=0.01)
    assert mp["dense_ffn"] / 1e6 == pytest.approx(72.35, abs=0.01)
    assert mp["routed_expert"] / 1e6 == pytest.approx(9.437, abs=0.001)
    assert mp["router"] == 64 * 2048 and mp["head"] == 8192 * 2048
    # 469.3 M trained: the tied table once, no lm_head
    assert "lm_head.w_0" not in count
    assert family.trained_parameters(sz) / 1e6 == pytest.approx(469.3,
                                                                abs=0.05)
    buffers = [n for n in count if family.reference.is_buffer(n)]
    assert len(buffers) == 4 and all(count[n] == 64 for n in buffers)
    assert family.trained_parameters(sz) * 12 / 1e9 == pytest.approx(
        5.63, abs=0.01)
    assert family.adam_routed_elements(sz) <= family.trained_parameters(sz)


def test_step_flops_by_hand():
    _, sz, tr = _cell()
    f = family.flops_per_step(sz, tr)
    # 186.1 M matmul parameters a token with uniform routing
    per_token = (4 * 16.777216 + 10.485760 + 72.351744 + 4 * 0.131072
                 + 4 * 0.5 * 9.437184 + 16.777216) * 1e6
    assert per_token / 1e6 == pytest.approx(186.1, abs=0.05)
    attn = 2 * 32 * 8192 * 8192 * 128 // 2
    assert f["attention_step"] == 3 * attn
    assert attn / 1e12 == pytest.approx(0.275, abs=0.001)
    assert f["step"] == pytest.approx(3 * (2 * 8192 * per_token + attn))
    assert f["step"] / 1e12 == pytest.approx(9.97, abs=0.01)
    assert f["conv_projections_step"] / f["step"] == pytest.approx(
        0.33, abs=0.01)
    assert f["routed_step"] / f["step"] == pytest.approx(0.093, abs=0.003)
    assert f["attention_step"] / f["step"] == pytest.approx(0.083, abs=0.003)
    # the convolution: 8 D bytes a token forward, 14 D backward, bf16
    assert family.short_conv_bytes_forward_per_token(sz) == 8 * 2048
    assert family.short_conv_bytes_backward_per_token(sz) == 14 * 2048
    pk = peaks.peaks("TPU v5 lite")
    least = family.short_conv_roofline_seconds_per_step(sz, tr, pk)
    assert least == pytest.approx(4 * 8192 * 22 * 2048 / 819e9)


def test_flops_and_bytes_follow_the_programs_counters():
    _, sz, tr = _cell()
    uniform = family.flops_per_step(sz, tr)
    sz[family._LOAD_KEY] = np.full((4, 8), 3 * 256, np.int64)   # half
    half = family.flops_per_step(sz, tr)
    assert half["routed_step"] == pytest.approx(uniform["routed_step"] / 2)
    assert half["dense_step"] == uniform["dense_step"]
    pk = peaks.peaks("TPU v5 lite")
    whole = family.short_conv_roofline_seconds_per_step(sz, tr, pk)
    sz[family._CONVOLVED_KEY] = np.full((4,), 4096, np.int64)
    assert family.short_conv_roofline_seconds_per_step(sz, tr, pk) \
        == pytest.approx(whole / 2)
    assert family.expert_load(sz).shape == (4, 8)
    assert family.convolved_tokens(sz).tolist() == [4096] * 4


def test_expected_routing_and_classifier():
    _, sz, tr = _cell()
    assert family.expected_routing(sz, tr) == {
        "fused_adam": "custom", "flash_attention": "custom",
        "moe_grouped_matmul": "custom", "gated_short_conv": "custom"}
    assert family.expected_routing(sz, tr, rehearsal=True) == {}
    for head, want in (("%gated_short_conv_fwd.3", "gated_short_conv"),
                       ("%gated_short_conv_bwd.1", "gated_short_conv"),
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
    assert row["name"] == "LFM2-24B-A2B" and len(row["config"]) == 20
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["configs"]
                 if e["name"] == "lfm2_24b_a2b"][0]
    assert entry["source"] == c.config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    for key, published in row["config"].items():
        if key in c.config["reduced"]:
            cut = c.config["reduced"][key]
            assert cut["run"] == c.config[key] != published, key
            if key != "layer_types":      # that one is described in words
                assert cut["published"] == published, key
        else:
            assert c.config[key] == published, key
    # the run's layers are published layers 0, 2, 3, 4, 5
    assert c.config["layer_types"] == [
        row["config"]["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert row["config"]["layer_types"].count("conv") == 30
    assert sz["layers"] == "CACCC" and sz["num_dense_layers"] == 1
    assert sz["router_experts"] == 64 and sz["experts_held"] == 8
    assert sz["vocab_held"] * 8 == 65536 and sz["head_dim"] == 64
    assert sz["tie_word_embeddings"] and sz["router_norm_epsilon"] == 1e-6
    assert c.config["deployment"]["chips_sharing_each_layer"] == 8
    assert tr["batch"] * tr["seq_len"] == 8192 and tr["pool"] == 8
    assert tr["fetch"] == "every_step" and c.row["chips"] == 1
    assert "512 rows" in c.row["why"] and "2 in 40" in c.row["why"]
    cfg = family.model_config(sz)
    assert cfg.mixers == ["conv", "attn", "conv", "conv", "conv"]
    assert cfg.dense_layers == {0} and cfg.rms_norm_eps == 1e-5
    assert cfg.head_dim == 64 and cfg.num_key_value_heads == 8


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


def _flip_the_filters(sess):
    """A program whose convolution weighs its taps the wrong way round
    (the newest token with the oldest tap's weight)."""
    import jax.numpy as jnp
    for n in sess.names:
        if n.endswith("_conv.w_0"):
            var = sess.scope.find_var(n)
            var.set_value(jnp.asarray(var.get_value())[:, ::-1])


def test_rehearsal_is_correct():
    res = _drive()
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("hook", [_leave_the_weights_unnormalised,
                                  _flip_the_filters],
                         ids=lambda h: h.__name__.strip("_"))
def test_fault_planted_in_the_program_is_not_correct(hook):
    res = _drive(hook=hook)
    assert res["correct"] is False
    over = [k for k, c in res["compared"].items() if c["value"] > c["limit"]]
    assert set(over) & set(compare.NUMBERS), res["compared"]


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_control_and_faults_are_not_correct(seed):
    c = Cell(CELL)
    sz, tr = family.sizes(c.config, True), family.traffic(c.traffic, True)
    pool = family.make_pool(sz, tr, seed)
    ref = family.run_reference(sz, tr, pool, seed, 3)
    ctl = family.run_reference(sz, tr, pool, seed, 3, precision="fp8")
    values, _ = compare.gaps(ctl, ref)
    assert not compare.judge(values, c.limits_for(True))[1], values
    for fault in FAULTS:
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
    return T.Op(f"%{head} = bf16[1,8192,2048]{{2,1,0}} custom-call(bf16[1,"
                f"8192,6144]{{2,1,0}} %a), custom_call_target="
                f"\"tpu_custom_call\"", 0, dur_ns)


def _ctx(convolved=None):
    _, sz, tr = _cell()
    if convolved is not None:
        sz[family._CONVOLVED_KEY] = convolved
    conv = [_kernel(f"gated_short_conv_{k}.{i}", ns)
            for k, ns in (("fwd", 400_000), ("bwd", 600_000))
            for i in range(4)] * 2
    flash = [_kernel("flash_attention_fwd.1", 5_000_000),
             _kernel("flash_attention_dkv.1", 9_000_000)] * 2
    return {"steps": 2, "chips": 1, "family": family, "sizes": sz,
            "traffic": tr, "peaks": peaks.peaks("TPU v5 lite"),
            "trace": {"n_devices": 1, "by_category_s": {},
                      "kernels": {"gated_short_conv": conv,
                                  "flash_attention": flash}}}


def test_readers_on_a_synthetic_ctx():
    ctx = _ctx(np.full((4,), 8192, np.int64))
    assert short_conv_ms_per_step.read(ctx) == pytest.approx(4 * (0.4 + 0.6))
    least = 4 * 8192 * 22 * 2048 / 819e9
    assert short_conv_roofline_pct.read(ctx) == pytest.approx(
        100 * least / 4e-3)
    assert 0 < short_conv_roofline_pct.read(ctx) < 100
    half = _ctx(np.full((4,), 4096, np.int64))
    assert short_conv_roofline_pct.read(half) == pytest.approx(
        short_conv_roofline_pct.read(ctx) / 2)
    flops = 3 * 2 * 32 * 8192 * 8192 * 128 // 2
    assert gqa64_flash_roofline_pct.read(ctx) == pytest.approx(
        100 * flops / 197e12 / 14e-3)
    assert 0 < gqa64_flash_roofline_pct.read(ctx) < 100


@pytest.mark.parametrize("reader", READERS,
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_nothing_to_read_is_none_not_zero(reader):
    # a program with neither kernel
    ctx = _ctx(np.full((4,), 8192, np.int64))
    ctx["trace"]["kernels"] = {"fused_adam": [_kernel("fused_adam.7", 10)]}
    assert reader.read(ctx) is None
    # a counter that never counted, and a family that reads none
    for convolved in (np.zeros((4,), np.int64), None):
        ctx = _ctx(convolved)
        if reader is short_conv_roofline_pct:
            assert reader.read(ctx) is None


@pytest.mark.parametrize("data", ["tbase_s4096_two_steps_named.events.json.gz",
                                  "kanana2_s4096_two_steps.events.json.gz"])
@pytest.mark.parametrize("reader", [short_conv_ms_per_step,
                                    short_conv_roofline_pct],
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_the_other_families_recorded_traces_give_nothing(reader, data):
    """Neither recorded trace has a convolution kernel and neither family
    reads a convolved-tokens counter (the parent under this PR's
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
    assert reader.read(ctx) is None


def test_every_new_entry_has_its_reader():
    """By NAME, not by position: a later PR appends."""
    cell = Cell(CELL)
    for m in cell.per_layer():
        assert callable(cells.layer_metric_reader(m["name"]))
    names = {m["name"] for m in cell.per_layer()}
    assert NEW <= names
    assert {"step_mfu_pct", "fused_adam_roofline_pct", "host_ms_per_step",
            "device_idle_pct", "xla_ops_ms_per_step"} <= names
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(new) == NEW
    assert all(m["workloads"] == [CELL] and m["moves"] == "items_per_s"
               and m["layer"] == "Pallas kernels"
               and m["source"] == "device_trace" for m in new.values())
    assert new["short_conv_ms_per_step"]["unit"] == "ms"
    assert new["short_conv_roofline_pct"]["unit"] == "%"
    assert [w for w in bench["workloads"] if w["name"] == CELL] == [cell.row]
    assert [c["name"] for c in bench["configs"]].count("lfm2_24b_a2b") == 1
