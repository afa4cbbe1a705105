"""The readers of the program's own names (PR 26): each on a synthetic
`ctx`, on the trace recorded at PR 25 (no names: every reader returns
None, never 0) and on the trace recorded at PR 26 beside it (kernels
named, `pt.*` spans on the harness's thread); and the accepted
classifier, which must give a named event the kernel it gave the same
event unnamed.

data/tbase_s4096_two_steps_named.events.json.gz: steps 2 and 3 of 4
traced through the benchmark's own Session at transformer_base B=4
S=4096 on 1 x TPU v5 lite (my chip run, PR 26), same format as the PR 25
file. The figures asserted on it were read by hand with plain loops.
"""
import collections
import gzip
import json
import os
import re

import pytest

from benchmark.families import transformer_encdec as family
from benchmark.layer_metrics import (
    _named, engine_python_idle_ms_per_step, flash_dkv_ms_per_step,
    flash_dq_ms_per_step, flash_fwd_calls_per_step, flash_fwd_ms_per_step,
    setup_first_dispatch_s, setup_trace_s)
from benchmark.lib import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE_READERS = [engine_python_idle_ms_per_step, flash_fwd_ms_per_step,
                 flash_dq_ms_per_step, flash_dkv_ms_per_step,
                 flash_fwd_calls_per_step]


def _classify(op):
    return family.classify_kernel(*T.signature(op), op.name)


def _ctx_of(name, steps=2):
    """The readers' ctx from a recorded file, reduced as
    `trace.summarize` reduces an xplane."""
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        raw = json.load(f)
    (_, events), = raw["devices"].items()
    (_, spans), = raw["host"].items()
    ops = [T.Op(*e) for e in events]
    spans = [T.Op(*s) for s in spans]
    kernels = collections.defaultdict(list)
    for op in ops:
        if T.is_pallas(op):
            kernels[_classify(op)].append(op)
    trace = {"kernels": dict(kernels), "n_devices": 1,
             "idle_gaps_s": T.idle_gaps(ops, spans)}
    return {"trace": trace, "steps": steps}, ops, spans


def _kernel(head, dur_ns, start_ns=0):
    text = (f"%{head} = bf16[4,4096,512]{{2,1,0}} custom-call(bf16[4,4096,"
            f"512]{{2,1,0}} %a), custom_call_target=\"tpu_custom_call\"")
    return T.Op(text, start_ns, dur_ns)


def test_head_strips_the_numeric_suffix_only():
    assert _named.head(_kernel("flash_attention_fwd.12", 1)) == \
        "flash_attention_fwd"
    assert _named.head(_kernel("flash_attention_fwd", 1)) == \
        "flash_attention_fwd"
    assert _named.head(_kernel("step1.177", 1)) == "step1"
    assert _named.head(T.Op("fused_adam.3", 0, 1)) == "fused_adam"


def test_readers_on_a_synthetic_ctx():
    flash = ([_kernel(f"flash_attention_fwd.{i}", 2_000_000)
              for i in range(8)]
             + [_kernel("flash_attention_fwd", 1_000_000)]
             + [_kernel(f"flash_attention_dq.{i}", 3_000_000)
                for i in range(4)]
             + [_kernel("flash_attention_dkv.1", 5_000_000)])
    ctx = {"steps": 2, "trace": {
        "n_devices": 1, "kernels": {"flash_attention": flash},
        "idle_gaps_s": {"pt.step": 0.002, "pt.engine.fetch": 0.004,
                        "pt.executor.feed": 0.001,
                        "bench.step": 0.5, "PjitFunction(step1)": 0.25}}}
    assert flash_fwd_ms_per_step.read(ctx) == pytest.approx(8.5)
    assert flash_dq_ms_per_step.read(ctx) == pytest.approx(6.0)
    assert flash_dkv_ms_per_step.read(ctx) == pytest.approx(2.5)
    assert flash_fwd_calls_per_step.read(ctx) == pytest.approx(4.5)
    assert engine_python_idle_ms_per_step.read(ctx) == pytest.approx(3.5)
    # two devices: per-device time and calls
    ctx["trace"]["n_devices"] = 2
    assert flash_fwd_ms_per_step.read(ctx) == pytest.approx(4.25)
    assert flash_fwd_calls_per_step.read(ctx) == pytest.approx(2.25)


@pytest.mark.parametrize("reader", TRACE_READERS,
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_nothing_to_read_is_none_not_zero(reader):
    # no kernel events at all, no gap under a span of the program
    empty = {"steps": 16, "trace": {"n_devices": 1, "kernels": {},
                                    "idle_gaps_s": {"bench.step": 0.06}}}
    assert reader.read(empty) is None
    # kernels there, but unnamed, as every commit before PR 26 has them
    unnamed = {"steps": 16, "trace": {
        "n_devices": 1,
        "kernels": {"flash_attention": [_kernel("step1.7", 1000)]},
        "idle_gaps_s": {"bench.step": 0.06, "np.asarray(jax.Array)": 0.03}}}
    assert reader.read(unnamed) is None


@pytest.mark.parametrize("reader", TRACE_READERS,
                         ids=lambda m: m.__name__.rsplit(".", 1)[1])
def test_the_pr25_trace_gives_nothing(reader):
    ctx, ops, _ = _ctx_of("tbase_s4096_two_steps.events.json.gz")
    assert len(ctx["trace"]["kernels"]["flash_attention"]) == 144
    assert reader.read(ctx) is None


def test_setup_readers(monkeypatch):
    from paddle_tpu.observability import tracing
    spans = [{"name": "trace_step", "kind": "setup", "dur_ms": 1500.0},
             {"name": "trace_step.op_walk", "kind": "setup",
              "dur_ms": 900.0},
             {"name": "first_dispatch", "kind": "setup",
              "dur_ms": 12000.0},
             {"name": "trace_step", "kind": "setup", "dur_ms": 2500.0},
             {"name": "first_dispatch", "kind": "setup", "dur_ms": 250.0}]
    monkeypatch.setattr(tracing, "setup_spans", lambda: list(spans),
                        raising=False)
    assert setup_trace_s.read({}) == pytest.approx(4.0)
    assert setup_first_dispatch_s.read({}) == pytest.approx(12.25)
    # a process that traced nothing
    monkeypatch.setattr(tracing, "setup_spans", lambda: [], raising=False)
    assert setup_trace_s.read({}) is None
    assert setup_first_dispatch_s.read({}) is None
    # a program without the list (the parent commit)
    monkeypatch.delattr(tracing, "setup_spans")
    assert setup_trace_s.read({}) is None
    assert setup_first_dispatch_s.read({}) is None


def test_classifier_gives_a_named_event_the_same_kernel():
    """Every Pallas event of the PR 25 trace, renamed as the program now
    names it, is classified as it was by its signature."""
    _, ops, _ = _ctx_of("tbase_s4096_two_steps.events.json.gz")
    pallas = [o for o in ops if T.is_pallas(o)]
    assert len(pallas) == 342
    names = {"fused_adam": ["fused_adam"],
             "flash_attention": ["flash_attention_fwd",
                                 "flash_attention_dq",
                                 "flash_attention_dkv"]}
    for op in pallas:
        was = _classify(op)
        assert was in names
        for name in names[was]:
            renamed = T.Op(re.sub(r"^%step1(\.\d+)? = ",
                                  lambda m: f"%{name}{m.group(1) or ''} = ",
                                  op.name), op.start_ns, op.dur_ns)
            assert renamed.name != op.name
            assert _classify(renamed) == was
    # and a kernel of neither family stays unknown by name
    other = T.Op(pallas[0].name.replace("%step1", "%fused_sgd", 1), 0, 1)
    assert "sgd" in other.name


# --------------------------------------------- the trace recorded at PR 26

NAMED = "tbase_s4096_two_steps_named.events.json.gz"


@pytest.fixture(scope="module")
def named():
    return _ctx_of(NAMED)


def test_named_trace_classifies_as_before(named):
    ctx, ops, _ = named
    pallas = [o for o in ops if T.is_pallas(o)]
    kinds = collections.Counter(_classify(o) for o in pallas)
    # 171 kernel calls a step as at PR 25: 99 fused_adam, 72 flash
    assert kinds == {"fused_adam": 198, "flash_attention": 144}
    heads = collections.Counter(_named.head(o) for o in pallas)
    assert heads == {"fused_adam": 198, "flash_attention_fwd": 72,
                     "flash_attention_dq": 36, "flash_attention_dkv": 36}


def test_named_trace_readers(named):
    ctx, ops, spans = named
    flash = ctx["trace"]["kernels"]["flash_attention"]
    assert flash_fwd_calls_per_step.read(ctx) == 36.0
    parts = [flash_fwd_ms_per_step.read(ctx),
             flash_dq_ms_per_step.read(ctx),
             flash_dkv_ms_per_step.read(ctx)]
    assert all(p is not None and p > 0 for p in parts)
    assert sum(parts) == pytest.approx(
        sum(o.dur_ns for o in flash) / 1e6 / 2)
    by_hand = collections.defaultdict(float)
    for o in flash:
        name = o.name[1:o.name.index(" =")]         # %name.N = ...
        stem, _, suffix = name.rpartition(".")
        by_hand[stem if suffix.isdigit() else name] += o.dur_ns / 2e6
    assert parts[0] == pytest.approx(by_hand["flash_attention_fwd"])
    assert parts[1] == pytest.approx(by_hand["flash_attention_dq"])
    assert parts[2] == pytest.approx(by_hand["flash_attention_dkv"])
    idle = engine_python_idle_ms_per_step.read(ctx)
    gaps = ctx["trace"]["idle_gaps_s"]
    assert idle == pytest.approx(
        1e3 / 2 * sum(v for k, v in gaps.items() if k.startswith("pt.")))
    assert any(k.startswith("pt.engine.") for k in gaps)
    # the harness's own span keeps next to nothing once the program's
    # spans lie inside it
    assert gaps.get("bench.step", 0.0) < 0.1 * sum(gaps.values())
    names = {s.name for s in spans}
    assert {"pt.step", "pt.executor.feed", "pt.engine.feed",
            "pt.engine.args", "pt.engine.rng", "pt.engine.dispatch",
            "pt.engine.writeback", "pt.engine.fetch",
            "pt.engine.release"} <= names
