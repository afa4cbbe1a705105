"""The `mamba_gqa_moe_decoder` family and the cell `twotower_s4096`:
parameter, operation and byte counts against numbers worked by hand
(ISSUE 35), the configuration against the catalog's row (a copy under
data/), the cell's rehearsal through the harness's own `run_cell` —
`correct` for the sound program, not for the float8 control nor for the
planted faults — and the two readers this cell brings, on synthetic
events and on the traces recorded of the other families (no scan kernel,
no counter: None, never 0)."""
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.families import mamba_gqa_moe_decoder as family
from benchmark.layer_metrics import ssd_ms_per_step, ssd_roofline_pct
from benchmark.lib import cells, compare, peaks
from benchmark.lib import trace as T
from benchmark.lib.cells import Cell

from test_layer_metrics_named import _ctx_of

CELL = "twotower_s4096"
READERS = [ssd_ms_per_step, ssd_roofline_pct]
# the faults the REHEARSAL's limits separate on every seed. Not among
# them: "state_dropped" and "decay_bf16" — at 40 tokens in chunks of 16 the
# state a chunk boundary drops is a few tokens old and no decay is slow
# enough to round to 1 (limits file, "rehearsal"); the chip's readings
# at 4,096 tokens are in the limits file's "rule"
FAULTS = ("norm_all_channels", "relu_unsquared", "unnormalised_topk",
          "half_positions")
CATALOG_ROW = os.path.join(os.path.dirname(__file__), "data",
                           "nemotron_twotower_30b_a3b.catalog_row.json")


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
    mp = family.matmul_params(sz)
    # the mixer: in 2688 x (4096 + 6144 + 64), out 4096 x 2688, conv 4 taps
    mixer = 2688 * 10304 + 4096 * 2688 + 6144 * 4
    assert mixer == mp["mixer"]
    whole = mixer + 6144 + 3 * 64 + 4096 + 2688     # + bias, dt/A/D, norms
    assert whole / 1e6 == pytest.approx(38.74, abs=0.01)
    attn = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
    assert attn == mp["attention"] and attn / 1e6 == pytest.approx(23.40, abs=0.01)
    assert mp["routed_expert"] == 2 * 2688 * 1856       # ungated: two
    assert mp["routed_expert"] / 1e6 == pytest.approx(9.98, abs=0.01)
    assert mp["shared"] / 1e6 == pytest.approx(19.96, abs=0.01)
    expert_layer = 8 * mp["routed_expert"] + mp["shared"] + mp["router"] \
        + 2688
    assert expert_layer / 1e6 == pytest.approx(100.13, abs=0.01)
    assert trained == 3 * whole + 3 * expert_layer + attn + 2688 \
        + 2 * 16384 * 2688 + 2688
    assert trained / 1e6 == pytest.approx(528.1, abs=0.05)
    assert sum(v for n, v in count.items()
               if family.reference.is_buffer(n)) == 3 * 128
    assert family.adam_routed_bytes_per_step(sz) / 1e9 == pytest.approx(
        14.8, abs=0.05)


def test_step_flops_by_hand():
    _, sz, tr = _cell()
    # the scan, forward a token a mixer: 2 H (Q P + 2 N P) + 2 G Q N
    scan = 2 * 64 * (128 * 64 + 2 * 128 * 64) + 2 * 8 * 128 * 128
    assert scan == 3_407_872 == family.ssd_flops_forward_per_token(sz)
    assert family.ssd_flops_backward_per_token(sz) == 2 * scan
    assert family.ssd_bytes_forward_per_token(sz) == \
        2 * (2 * 4096 + 2 * 1024) + 4 * 64 == 20_736
    assert family.ssd_bytes_backward_per_token(sz) == \
        2 * (3 * 4096 + 4 * 1024) + 8 * 64 == 33_280
    assert family.scanned_tokens_per_step(sz, tr) == 3 * 4096
    f = family.flops_per_step(sz, tr)
    assert f["scan_step"] == 3 * 3 * 4096 * scan
    one_attention = 2 * 32 * 4096 * 4096 * 256 // 2
    assert f["attention_step"] == 3 * one_attention
    # uniform routing: 6 * 8 / 128 of a row a token a layer, 192 an expert
    assert family.routed_rows_per_step(sz, tr) == 3 * 1536
    assert f["routed_step"] == 3 * 2 * 3 * 1536 * 2 * 2688 * 1856
    mp = family.matmul_params(sz)
    per_token = 3 * mp["mixer"] + mp["attention"] \
        + 3 * (128 * 2688 + mp["shared"]) + 2688 * 16384
    assert f["dense_step"] == 3 * 2 * 4096 * per_token
    assert f["step"] == f["dense_step"] + f["routed_step"] \
        + f["attention_step"] + f["scan_step"]
    assert f["step"] / 1e12 == pytest.approx(6.8, abs=0.1)
    mixers = 3 * 2 * 4096 * 3 * mp["mixer"] + f["scan_step"]
    assert mixers / f["step"] == pytest.approx(0.44, abs=0.01)
    # the roofline's least time: memory bounds both passes
    p = peaks.peaks("TPU v5 lite")
    least = family.ssd_roofline_seconds_per_step(sz, tr, p)
    assert least == pytest.approx(
        3 * 4096 * (20_736 + 33_280) / 819e9, rel=1e-6)
    assert 3 * 4096 * 3 * scan / 197e12 < least


def test_flops_follow_the_programs_counters():
    _, sz, tr = _cell()
    uniform = family.flops_per_step(sz, tr)
    # the scanned-tokens counter is OVERWRITTEN: what it holds is one step's
    sz[family._SCANNED_KEY] = np.full((3,), 2048, np.int64)
    assert family.scanned_tokens_per_step(sz, tr) == 3 * 2048
    assert family.flops_per_step(sz, tr)["scan_step"] == \
        uniform["scan_step"] / 2
    # the expert-load counter ADDS UP over the three proof steps
    sz[family._LOAD_KEY] = np.full((3, 8), 3 * 100, np.int64)
    assert family.routed_rows_per_step(sz, tr) == 3 * 8 * 100
    assert family.flops_per_step(sz, tr)["routed_step"] == pytest.approx(
        uniform["routed_step"] * 800 / 1536)


def test_expected_routing_and_classifier():
    _, sz, tr = _cell()
    assert family.expected_routing(sz, tr) == {
        "fused_adam": "custom", "flash_attention": "custom",
        "moe_grouped_matmul": "custom", "mamba2_ssd": "custom"}
    assert family.expected_routing(sz, tr, rehearsal=True) == {}
    for head, kernel in (("%mamba2_ssd_fwd.3", "mamba2_ssd"),
                         ("%mamba2_ssd_bwd", "mamba2_ssd"),
                         ("%moe_grouped_matmul_dw.7", "moe_grouped_matmul"),
                         ("%flash_attention_dkv", "flash_attention"),
                         ("%fused_adam.12", "fused_adam"),
                         ("%sparse_index_scores.1", None)):
        assert family.classify_kernel([], [], head + " = f32[8] x") == kernel


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    c, sz, tr = _cell()
    with open(CATALOG_ROW) as f:      # the published row, copied whole
        row = json.load(f)
    assert row["name"] == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16"
    assert len(row["config"]) == 47
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["configs"]
                 if e["name"] == "nemotron_twotower_30b_a3b"][0]
    assert entry["source"] == c.config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"])
    for key, published in row["config"].items():
        if key in c.config["reduced"]:
            cut = c.config["reduced"][key]
            assert cut["published"] == published != cut["run"] \
                == c.config[key], key
        else:
            assert c.config[key] == published, key
    # no width is cut: only depth (and its pattern), experts and vocabulary
    assert sorted(c.config["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert row["config"]["hybrid_override_pattern"].startswith(sz["pattern"])
    assert sz["pattern"] == "MEMEM*E" and len(sz["pattern"]) == 7
    assert sz["router_experts"] == 128 and sz["experts_held"] == 8
    assert sz["vocab_held"] * 8 == 131072
    assert (sz["mamba_num_heads"], sz["mamba_head_dim"], sz["n_groups"],
            sz["ssm_state_size"], sz["conv_kernel"], sz["chunk_size"]) \
        == (64, 64, 8, 128, 4, 128)
    assert c.config["deployment"]["chips_sharing_each_layer"] == 16
    assert c.config["assumed"]["second_tower"] == "not built"
    assert tr["batch"] * tr["seq_len"] == 4096 and tr["pool"] == 8
    assert tr["fetch"] == "every_step" and c.row["chips"] == 1
    assert "16th" in c.row["why"] and "192 rows" in c.row["why"]
    # the model file takes the family's keys and builds seven one-part
    # layers from them
    cfg = family.model_config(sz)
    assert cfg.parts == ["mamba", "moe", "mamba", "moe", "mamba", "attn",
                         "moe"]
    assert not cfg.gated_ffn and not cfg.attention_positions
    assert cfg.shared_expert_width == 3712 and cfg.rms_norm_eps == 1e-5


# ----------------------------------------------- correct, control, fault

def _drive(hook=None, seed=13):
    out, err = io.StringIO(), io.StringIO()
    res = bench_run.run_cell(Cell(CELL), seed, 0.3, 0, True,
                             time.perf_counter(), session_hook=hook,
                             out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def _shift_the_steps_bias(sess):
    """A program whose scan decays wrongly. The chunk size changes no
    result, so no attribute plants that: the step sizes' bias is moved
    by one instead — every dt, and with it every decay, is wrong."""
    import jax.numpy as jnp
    for n in sess.names:
        if n.endswith("_mixer_dt.b_0"):
            var = sess.scope.find_var(n)
            var.set_value(jnp.asarray(var.get_value()) - 1.0)


def _leave_the_weights_unnormalised(sess):
    for op in sess.main.global_block().ops:
        if op.type in ("moe_router", "moe_router_grad"):
            op.set_attr("norm_topk_prob", False)


def _norm_over_all_channels(sess):
    for op in sess.main.global_block().ops:
        if op.type in ("gated_rms_norm", "gated_rms_norm_grad"):
            op.set_attr("groups", 1)


def test_rehearsal_is_correct():
    res = _drive()
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("hook", [_shift_the_steps_bias,
                                  _leave_the_weights_unnormalised,
                                  _norm_over_all_channels],
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
    same, _ = compare.gaps(ref, ref)
    assert compare.judge(same, c.limits_for(True))[1]


def test_the_shipped_limits_separate_the_chips_readings():
    """What the limits file records of the chip: the program's largest
    reading under each limit that is held, and the control and each
    fault the limits are there for over at least one; the one fault no
    limit can see is recorded as such."""
    limits = Cell(CELL).limits
    r = limits["readings"]
    held = [n for n in compare.NUMBERS if n in limits]
    assert held == ["grad_gap", "delta_gap", "grad_dir_gap"]
    assert "loss_gap" in limits["not_compared"]
    for number in held:
        assert r[number]["lower"] < limits[number], number
        assert max(r[number]["program_all"]) == r[number]["lower"]
        assert r[number]["runs"] == len(r[number]["program_all"]) >= 10
    for who in ("control", "mm_only", "state_dropped_min",
                "norm_all_channels_min", "relu_unsquared_min",
                "unnormalised_topk_min", "half_positions_min"):
        over = []
        for number in held:
            got = r[number][who]
            worst = min((x if x is not None else np.inf) for x in got) \
                if isinstance(got, list) else got
            over.append(worst > limits[number])
        assert any(over), who
    # the direction gap is twice its lower reading, and every fault it
    # is there for reads at least 1.7 times the limit
    assert limits["grad_dir_gap"] >= 2 * r["grad_dir_gap"]["lower"]
    assert r["grad_dir_gap"]["state_dropped_min"] > 1.7 * limits["grad_dir_gap"]
    assert limits["delta_gap"] < r["delta_gap"]["state_unchanged"] == 1.0
    # a decay in bfloat16 reads under the program's own rounding: said so
    assert max(r["grad_dir_gap"]["decay_bf16"]) < limits["grad_dir_gap"]
    assert "NOT CAUGHT" in limits["rule"] and "decay_bf16" in limits["rule"]
    assert {"grad_gap", "delta_gap", "grad_dir_gap"} <= set(limits["rehearsal"])


# ------------------------------------------------------------ the readers

def _kernel(head, dur_ns):
    return T.Op(f"%{head} = bf16[1,4096,4096]{{2,1,0}} custom-call(bf16[1,"
                f"4096,4096]{{2,1,0}} %a), custom_call_target="
                f"\"tpu_custom_call\"", 0, dur_ns)


def _ctx(scanned=None):
    _, sz, tr = _cell()
    if scanned is not None:
        sz[family._SCANNED_KEY] = scanned
    scan = [_kernel(f"mamba2_ssd_{k}.{i}", ns)
            for k, ns in (("fwd", 1_000_000), ("bwd", 3_000_000))
            for i in range(3)] * 2
    return {"steps": 2, "chips": 1, "family": family, "sizes": sz,
            "traffic": tr, "peaks": peaks.peaks("TPU v5 lite"),
            "trace": {"n_devices": 1, "by_category_s": {},
                      "kernels": {"mamba2_ssd": scan}}}


def test_readers_on_a_synthetic_ctx():
    ctx = _ctx(np.full((3,), 4096, np.int64))
    assert ssd_ms_per_step.read(ctx) == pytest.approx(3 * (1.0 + 3.0))
    least = 3 * 4096 * (20_736 + 33_280) / 819e9
    assert ssd_roofline_pct.read(ctx) == pytest.approx(
        100 * least / 12e-3)
    assert 0 < ssd_roofline_pct.read(ctx) < 100
    # half the tokens scanned: half the needed work over the same time
    half = _ctx(np.full((3,), 2048, np.int64))
    assert ssd_roofline_pct.read(half) == pytest.approx(
        ssd_roofline_pct.read(ctx) / 2)


@pytest.mark.parametrize("reader", READERS,
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_nothing_to_read_is_none_not_zero(reader):
    # a program with no scan kernel
    ctx = _ctx(np.full((3,), 4096, np.int64))
    ctx["trace"]["kernels"] = {"fused_adam": [_kernel("fused_adam.7", 10)]}
    assert reader.read(ctx) is None
    # a counter that never counted, and a family that reads none
    for scanned in (np.zeros((3,), np.int64), None):
        ctx = _ctx(scanned)
        if reader is ssd_roofline_pct:
            assert reader.read(ctx) is None


@pytest.mark.parametrize("data", ["tbase_s4096_two_steps_named.events.json.gz",
                                  "kanana2_s4096_two_steps.events.json.gz"])
@pytest.mark.parametrize("reader", READERS,
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_the_other_families_recorded_traces_give_nothing(reader, data):
    """Neither recorded trace has a scan kernel and neither family reads
    a scanned-tokens counter: a parent program reads None, never 0."""
    import collections
    import gzip
    path = os.path.join(os.path.dirname(__file__), "data", data)
    if not os.path.exists(path):
        pytest.skip("no such recorded trace")
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
    for m in Cell(CELL).per_layer():
        assert callable(cells.layer_metric_reader(m["name"]))
    names = {m["name"] for m in Cell(CELL).per_layer()}
    assert {r.__name__.rsplit(".", 1)[1] for r in READERS} <= names
    # the accepted metrics that list other cells do not report here
    assert not names & {"flash_attn_roofline_pct", "mla_flash_roofline_pct",
                        "moe_gmm_ms_per_step", "dsa_index_ms_per_step"}
    assert {"step_mfu_pct", "fused_adam_roofline_pct", "host_ms_per_step",
            "device_idle_pct", "xla_ops_ms_per_step"} <= names
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"]
           if m["name"] in {r.__name__.rsplit(".", 1)[1] for r in READERS}]
    assert all(m["workloads"] == [CELL] and m["moves"] == "items_per_s"
               and m["layer"] == "Pallas kernels" for m in new)
    assert len(new) == 2 and bench["per_layer"][-2:] == new
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "nemotron_twotower_30b_a3b"
    # the accepted expert-layer and flash readers read this family too
    # (their `workloads` lists are the benchmark's to extend)
    from benchmark.layer_metrics import (
        moe_gmm_roofline_pct, moe_held_rows_per_token)
    ctx = _ctx()
    ctx["sizes"][family._LOAD_KEY] = np.full((3, 8), 3 * 192, np.int64)
    ctx["trace"]["kernels"]["moe_grouped_matmul"] = [
        _kernel("moe_grouped_matmul_fwd.1", 20_000_000)]
    assert moe_held_rows_per_token.read(ctx) == pytest.approx(0.375)
    assert 0 < moe_gmm_roofline_pct.read(ctx) < 100
