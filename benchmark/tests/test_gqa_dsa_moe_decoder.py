"""The `gqa_dsa_moe_decoder` family and the cell `keye2_s8192`: parameter,
operation and byte counts against numbers worked by hand (ISSUE 33), the
configuration against the catalog's row (a copy under data/), the cell's
rehearsal through the harness's own `run_cell` — `correct` for the sound
program, not for the float8 control nor for any of the four planted
faults — and the five readers this cell brings, on synthetic events and
on the traces recorded of the other families (no index kernels, no
counter: None, never 0)."""
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.families import gqa_dsa_moe_decoder as family
from benchmark.layer_metrics import (
    dsa_index_ms_per_step, dsa_index_roofline_pct, dsa_kept_pairs_pct,
    dsa_select_roofline_pct, sparse_attn_roofline_pct)
from benchmark.lib import cells, compare, peaks
from benchmark.lib import trace as T
from benchmark.lib.cells import Cell

from test_layer_metrics_named import _ctx_of

CELL = "keye2_s8192"
READERS = [sparse_attn_roofline_pct, dsa_index_roofline_pct,
           dsa_index_ms_per_step, dsa_kept_pairs_pct,
           dsa_select_roofline_pct]
FAULTS = ("dense_attention", "half_topk", "unnormalised_topk",
          "half_positions")
CATALOG_ROW = os.path.join(os.path.dirname(__file__), "data",
                           "keye_vl2_30b_a3b.catalog_row.json")


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
    buffers = sum(v for n, v in count.items()
                  if family.reference.is_buffer(n))
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048      # q, k, v, o
    assert attn == 18_874_368 == family.matmul_params(sz)["attention"]
    experts = 16 * 3 * 2048 * 768
    assert experts == 75_497_472
    layer = attn + 128 * 2048 + experts + 2 * 2048 + 2 * 128   # + norms
    assert layer / 1e6 == pytest.approx(94.64, abs=0.01)
    assert trained == 4 * layer + 2 * 18992 * 2048 + 2048
    assert trained / 1e6 == pytest.approx(456.3, abs=0.05)
    indexer = 2048 * (16 * 64 + 64 + 16)
    assert indexer == 2_260_992 == family.matmul_params(sz)["indexer"]
    assert buffers == 4 * (indexer + 2 * 64)
    assert buffers / 1e6 == pytest.approx(9.0, abs=0.05)
    # five buffers a layer, all the indexer's; k and v leave at 4 heads
    assert sum(family.reference.is_buffer(n) for n in count) == 20
    assert count["layer_2_attn_k.w_0"] == 2048 * 4 * 128
    assert len(specs) == 1 + 4 * 17 + 2
    # every matrix is over the kernel's floor; the norms are not
    assert family.adam_routed_elements(sz) == trained - (
        4 * (2 * 2048 + 2 * 128) + 2048)
    assert family.adam_routed_bytes_per_step(sz) == \
        28 * family.adam_routed_elements(sz)


def test_step_flops_by_hand():
    _, sz, tr = _cell()
    kept = sum(min(t + 1, 2048) for t in range(8192))
    assert kept == 14_681_088
    assert family.reference.kept_pairs_by_hand(1, 8192, 2048) == kept
    causal = 8192 * 8193 // 2
    assert causal == 33_558_528 == family.causal_pairs(tr)
    assert kept / causal == pytest.approx(0.4375, abs=1e-4)
    f = family.flops_per_step(sz, tr)
    # attention over the KEPT pairs, four layers, three forwards
    one_attention = 2 * 32 * kept * (128 + 128)
    assert family.attention_flops_forward(sz, tr) == 4 * one_attention
    assert f["attention_step"] == 12 * one_attention
    assert one_attention / 1e12 == pytest.approx(0.24, abs=0.005)
    # the index scores over ALL causal pairs, forward only
    assert f["index_step"] == 4 * 2 * 16 * 64 * causal
    assert f["index_step"] / 4e12 == pytest.approx(0.069, abs=0.001)
    # uniform routing: 8 * 16 / 128 = 1 row a token a layer
    assert family.routed_rows_per_step(sz, tr) == 4 * 8192
    assert f["routed_step"] == 3 * 2 * 4 * 8192 * 3 * 2048 * 768
    per_token = 4 * (18_874_368 + 128 * 2048) + 2048 * 18992
    indexer = 2 * 8192 * 4 * 2_260_992                  # forward only
    assert f["dense_step"] == 3 * 2 * 8192 * per_token + indexer
    assert f["step"] == f["dense_step"] + f["routed_step"] \
        + f["attention_step"] + f["index_step"]
    assert f["step"] / 1e12 == pytest.approx(9.9, abs=0.1)
    assert family.index_select_bytes(sz, tr) == 4 * 8192 * 8192 * 5


def test_flops_follow_the_programs_counters():
    _, sz, tr = _cell()
    uniform = family.flops_per_step(sz, tr)
    # the kept-pairs counter is OVERWRITTEN: what it holds is one step's
    sz[family._KEPT_KEY] = np.full((4,), 15_000_000, np.int64)
    assert family.kept_pairs_per_step(sz, tr) == 60_000_000
    got = family.flops_per_step(sz, tr)
    assert got["attention_step"] == pytest.approx(
        uniform["attention_step"] * 15_000_000 / 14_681_088)
    assert got["index_step"] == uniform["index_step"]
    # the expert-load counter ADDS UP over the three proof steps
    sz[family._LOAD_KEY] = np.full((4, 16), 3 * 400, np.int64)
    assert family.routed_rows_per_step(sz, tr) == 4 * 16 * 400
    assert family.flops_per_step(sz, tr)["routed_step"] == pytest.approx(
        uniform["routed_step"] * (16 * 400) / 8192)


def test_expected_routing_and_classifier():
    _, sz, tr = _cell()
    assert family.expected_routing(sz, tr) == {
        "fused_adam": "custom", "flash_attention": "custom",
        "moe_grouped_matmul": "custom", "sparse_index_scores": "custom"}
    assert family.expected_routing(sz, tr, rehearsal=True) == {}
    short = dict(tr, seq_len=512)
    assert family.expected_routing(sz, short)["sparse_index_scores"] == \
        family.expected_routing(sz, short)["flash_attention"] == "lowered"
    for head, kernel in (("%sparse_index_scores.3", "sparse_index_scores"),
                         ("%sparse_index_select", "sparse_index_select"),
                         ("%moe_grouped_matmul_dw.7", "moe_grouped_matmul"),
                         ("%flash_attention_dkv", "flash_attention"),
                         ("%fused_adam.12", "fused_adam"),
                         ("%fused_sgd", None)):
        assert family.classify_kernel([], [], head + " = f32[8] x") == kernel


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    c, sz, tr = _cell()
    with open(CATALOG_ROW) as f:      # the published row, copied whole
        row = json.load(f)
    assert row["name"] == "Keye-VL-2.0-30B-A3B" and len(row["config"]) == 26
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["configs"]
                 if e["name"] == "keye_vl2_30b_a3b"][0]
    assert entry["source"] == c.config["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"])
    for key, published in row["config"].items():
        if key in c.config["reduced"]:
            cut = c.config["reduced"][key]
            assert cut["published"] == published != cut["run"] \
                == c.config[key], key
        else:
            assert c.config[key] == published, key
    # no width is cut: only depth, experts held and vocabulary rows held
    assert sorted(c.config["reduced"]) == [
        "num_experts", "num_hidden_layers", "num_local_experts",
        "vocab_size"]
    assert sz["router_experts"] == 128 and sz["experts_held"] == 16
    assert sz["vocab_held"] * 8 == 151936 and sz["num_hidden_layers"] == 4
    assert (sz["index_heads"], sz["index_head_dim"], sz["index_topk"]) \
        == (16, 64, 2048)
    assert c.config["deployment"]["chips_sharing_each_layer"] == 8
    assert c.config["assumed"]["indexer_training"] == "frozen"
    assert tr["batch"] * tr["seq_len"] == 8192 and tr["pool"] == 8
    assert tr["fetch"] == "every_step" and c.row["chips"] == 1
    assert "eighth" in c.row["why"] and "512 rows" in c.row["why"]


# ----------------------------------------------- correct, control, fault

def _drive(hook=None, seed=13):
    out, err = io.StringIO(), io.StringIO()
    res = bench_run.run_cell(Cell(CELL), seed, 0.3, 0, True,
                             time.perf_counter(), session_hook=hook,
                             out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    return res


def _select_half_as_many(sess):
    for op in sess.main.global_block().ops:
        if op.type == "sparse_attention_index":
            op.set_attr("top_k", op.attr("top_k") // 2)


def _leave_the_weights_unnormalised(sess):
    for op in sess.main.global_block().ops:
        if op.type in ("moe_router", "moe_router_grad"):
            op.set_attr("norm_topk_prob", False)


def test_rehearsal_is_correct():
    res = _drive()
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("hook", [_select_half_as_many,
                                  _leave_the_weights_unnormalised],
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
    # the frozen indexer: no buffer moved in the reference either
    assert max(ref["buffer_delta_norms"].values()) < 1e-6


def test_the_shipped_limits_separate_the_chips_readings():
    """What the limits file records of the chip: the program's largest
    reading under each limit, and each fault and the control over at
    least one."""
    limits = Cell(CELL).limits
    r = limits["readings"]
    for number in compare.NUMBERS:            # all four are held
        assert r[number]["lower"] < limits[number], number
        assert max(r[number]["program_all"]) == r[number]["lower"]
    for who in ("control", "dense_attention_min", "half_topk_min",
                "unnormalised_topk_min", "half_positions_min"):
        over = []
        for number in compare.NUMBERS:
            got = r[number][who]
            worst = min((x if x is not None else np.inf) for x in got) \
                if isinstance(got, list) else got
            over.append(worst is None or worst > limits[number])
        assert any(over), who
    assert limits["delta_gap"] < r["delta_gap"]["state_unchanged"] == 1.0
    # the loss is held for a step that reports a wrong loss: half the
    # positions left out reads three times the limit, the control no number
    assert r["loss_gap"]["half_positions_min"] > 3 * limits["loss_gap"]
    assert r["loss_gap"]["control"] == [None] * 3
    assert set(compare.NUMBERS) <= set(limits["rehearsal"])


# ------------------------------------------------------------ the readers

def _kernel(head, dur_ns):
    return T.Op(f"%{head} = f32[1,8192,8192]{{2,1,0}} custom-call(bf16[1,"
                f"8192,1024]{{2,1,0}} %a), custom_call_target="
                f"\"tpu_custom_call\"", 0, dur_ns)


def _ctx(kept=None):
    _, sz, tr = _cell()
    if kept is not None:
        sz[family._KEPT_KEY] = kept
    flash = [_kernel(f"flash_attention_{k}.{i}", ns)
             for k, ns in (("fwd", 8_000_000), ("dkv", 17_000_000))
             for i in range(4)] * 2
    scores = [_kernel(f"sparse_index_scores.{i}", 1_500_000)
              for i in range(4)] * 2
    select = [_kernel(f"sparse_index_select.{i}", 3_500_000)
              for i in range(4)] * 2
    return {"steps": 2, "chips": 1, "family": family, "sizes": sz,
            "traffic": tr, "peaks": peaks.peaks("TPU v5 lite"),
            "trace": {"n_devices": 1, "by_category_s": {"xla:sort": 0.004},
                      "kernels": {"flash_attention": flash,
                                  "sparse_index_scores": scores,
                                  "sparse_index_select": select}}}


def test_readers_on_a_synthetic_ctx():
    kept = np.asarray([14_681_088, 14_681_090, 14_681_088, 14_681_100],
                      np.int64)
    ctx = _ctx(kept)
    attention = 3 * 2 * 32 * kept.sum() * 256
    assert sparse_attn_roofline_pct.read(ctx) == pytest.approx(
        100 * attention / 197e12 / 100e-3)
    index = 4 * 2 * 16 * 64 * 33_558_528
    assert dsa_index_roofline_pct.read(ctx) == pytest.approx(
        100 * index / 197e12 / 6e-3)
    assert dsa_index_ms_per_step.read(ctx) == pytest.approx(6.0 + 14.0)
    assert dsa_kept_pairs_pct.read(ctx) == pytest.approx(
        100 * kept.mean() / 33_558_528)
    assert dsa_kept_pairs_pct.read(ctx) == pytest.approx(43.75, abs=0.01)
    assert dsa_select_roofline_pct.read(ctx) == pytest.approx(
        100 * 4 * 8192 * 8192 * 5 / 819e9 / 14e-3)
    assert all(0 < r.read(ctx) <= 100 for r in READERS)
    # no selection kernel: nothing stands in for it (the router sorts too)
    del ctx["trace"]["kernels"]["sparse_index_select"]
    assert dsa_index_ms_per_step.read(ctx) is None
    assert dsa_select_roofline_pct.read(ctx) is None


@pytest.mark.parametrize("reader", READERS,
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_nothing_to_read_is_none_not_zero(reader):
    # a program with no index kernels, no flash events and no counter
    ctx = _ctx()
    ctx["trace"]["kernels"] = {"fused_adam": [_kernel("fused_adam.7", 10)]}
    assert reader.read(ctx) is None
    # a counter that never counted
    ctx = _ctx(np.zeros((4,), np.int64))
    if reader in (dsa_kept_pairs_pct, sparse_attn_roofline_pct):
        assert reader.read(ctx) is None


@pytest.mark.parametrize("data", ["tbase_s4096_two_steps_named.events.json.gz",
                                  "kanana2_s4096_two_steps.events.json.gz"])
@pytest.mark.parametrize("reader", READERS,
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_the_other_families_recorded_traces_give_nothing(reader, data):
    """Neither recorded trace has an index kernel, and neither family
    reads a kept-pairs counter: flash events alone are not enough for
    `sparse_attn_roofline_pct`."""
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
                        "moe_gmm_ms_per_step"}
    assert {"step_mfu_pct", "fused_adam_roofline_pct", "host_ms_per_step",
            "device_idle_pct", "xla_ops_ms_per_step"} <= names
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"]
           if m["name"] in {r.__name__.rsplit(".", 1)[1] for r in READERS}]
    assert all(m["workloads"] == [CELL] and m["moves"] == "items_per_s"
               for m in new) and len(new) == 5
