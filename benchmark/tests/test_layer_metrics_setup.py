"""The six readers of a job's set-up (`layer_metrics/_setup.py`) on a
recorded job — the set-up spans and totals of one warm `tbase_s128` run
on the chip (PR 37; `data/setup_tbase_s128_warm.json`) — and on
programs without the names, which give every reader nothing (None,
never 0)."""
import json
import os

import pytest

from benchmark.layer_metrics import (
    setup_cache_hit_pct, setup_compile_or_load_s, setup_first_dispatch_s,
    setup_first_execute_s, setup_infer_shapes_s, setup_jit_trace_s,
    setup_lower_s)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = (setup_infer_shapes_s, setup_jit_trace_s, setup_lower_s,
           setup_compile_or_load_s, setup_cache_hit_pct,
           setup_first_execute_s)


def _plant(monkeypatch, spans, build=None, compiles=None):
    """The program's tracing module holding `spans` and the totals; a
    total left None is a program without that counter."""
    from paddle_tpu.observability import tracing
    monkeypatch.setattr(tracing, "setup_spans", lambda: list(spans),
                        raising=False)
    for name, value in (("build_totals", build),
                        ("compile_totals", compiles)):
        if value is None:
            monkeypatch.delattr(tracing, name, raising=False)
        else:
            monkeypatch.setattr(tracing, name, lambda v=value: v,
                                raising=False)


@pytest.fixture
def recorded():
    with open(os.path.join(DATA, "setup_tbase_s128_warm.json")) as f:
        return json.load(f)


def _seconds(spans, *names):
    return sum(s["dur_ms"] for s in spans if s["name"] in names) / 1e3


def test_the_readers_on_a_recorded_job(monkeypatch, recorded):
    spans = recorded["spans"]
    _plant(monkeypatch, spans, recorded["build_totals"],
           recorded["compile_totals"])
    read = {m.__name__.rsplit(".", 1)[1]: m.read({}) for m in READERS}
    assert read["setup_infer_shapes_s"] == pytest.approx(
        recorded["build_totals"]["seconds"])
    assert read["setup_infer_shapes_s"] == pytest.approx(sum(
        r["seconds"] for r in recorded["build_totals"]["by_op"].values()))
    assert read["setup_jit_trace_s"] == pytest.approx(
        _seconds(spans, "first_dispatch.jit_trace"))
    assert read["setup_lower_s"] == pytest.approx(
        _seconds(spans, "first_dispatch.lower"))
    assert read["setup_compile_or_load_s"] == pytest.approx(_seconds(
        spans, "first_dispatch.compile", "first_dispatch.cache_load"))
    # a warm process: every compile request of a first dispatch that the
    # persistent cache counted was a hit, and no span is a `.compile`
    assert read["setup_cache_hit_pct"] == 100.0
    assert not _seconds(spans, "first_dispatch.compile")
    # the children and the remainder are the parent
    assert read["setup_first_execute_s"] > 0
    assert (read["setup_jit_trace_s"] + read["setup_lower_s"]
            + read["setup_compile_or_load_s"]
            + read["setup_first_execute_s"]) == pytest.approx(
        setup_first_dispatch_s.read({}))
    # two executables (startup, main), each with its three children
    parents = [s for s in spans if s["name"] == "first_dispatch"]
    assert len(parents) == 2
    for p in parents:
        assert len([s for s in spans if s["parent"] == p["span"]]) == 3


def test_a_cold_job_reads_the_compile(monkeypatch, recorded):
    """The same job as a checkout's first process would see it: the
    loads turned into compiles, the cache's answers into misses."""
    spans = [dict(s, name=s["name"].replace("cache_load", "compile"))
             for s in recorded["spans"]]
    inside = dict(recorded["compile_totals"]["first_dispatch"])
    inside.update(cache_misses=inside["cache_hits"], cache_hits=0)
    _plant(monkeypatch, spans, recorded["build_totals"],
           {"first_dispatch": inside,
            "outside": recorded["compile_totals"]["outside"]})
    assert setup_cache_hit_pct.read({}) == 0.0
    assert setup_compile_or_load_s.read({}) == pytest.approx(
        _seconds(spans, "first_dispatch.compile"))


def test_a_job_whose_requests_never_used_the_cache(monkeypatch, recorded):
    inside = dict(recorded["compile_totals"]["first_dispatch"],
                  cache_hits=0, cache_misses=0)
    _plant(monkeypatch, recorded["spans"], recorded["build_totals"],
           {"first_dispatch": inside, "outside": inside})
    assert setup_cache_hit_pct.read({}) is None     # not 0%


def test_the_parent_commit_gives_nothing(monkeypatch):
    """PR 36's program: the three set-up spans it kept, no children, no
    counters. The two accepted readers read; the six new ones do not."""
    spans = [{"name": "trace_step.op_walk", "kind": "setup", "span": "p.s2",
              "parent": "p.s1", "dur_ms": 3900.0},
             {"name": "trace_step", "kind": "setup", "span": "p.s1",
              "parent": None, "dur_ms": 4000.0},
             {"name": "first_dispatch", "kind": "setup", "span": "p.s3",
              "parent": None, "dur_ms": 13000.0}]
    _plant(monkeypatch, spans)
    assert setup_first_dispatch_s.read({}) == pytest.approx(13.0)
    assert [m.read({}) for m in READERS] == [None] * len(READERS)


def test_a_process_that_ran_nothing(monkeypatch):
    from paddle_tpu.observability import tracing
    tracing.compile_totals()            # the real counters, at zero
    _plant(monkeypatch, [], {"calls": 0, "seconds": 0.0, "by_op": {}},
           {side: dict.fromkeys(
               ("jit_trace_s", "lower_s", "compile_s", "cache_load_s",
                "cache_retrieval_s", "cache_hits", "cache_misses"), 0)
            for side in ("first_dispatch", "outside")})
    assert [m.read({}) for m in READERS] == [None] * len(READERS)


def test_a_program_without_the_list(monkeypatch):
    from paddle_tpu.observability import tracing
    _plant(monkeypatch, [])
    monkeypatch.delattr(tracing, "setup_spans")
    assert [m.read({}) for m in READERS] == [None] * len(READERS)
