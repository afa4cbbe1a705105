"""Set-up from the inside (docs/TRACING.md, "Set-up"): a set-up span or
a counter for every phase a job pays before its first steady step —
`import`, the `program_build.*` passes, one `cold_run` a cold
`Executor.run` holding `trace_step` and `first_dispatch`, the children
`jax.monitoring` times inside a first dispatch — the process totals
beside them, and the benchmark's six readers of both.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax.monitoring

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.scope import Scope
from paddle_tpu.observability import tracing

from benchmark.layer_metrics import (
    setup_cache_hit_pct, setup_compile_or_load_s, setup_first_execute_s,
    setup_infer_shapes_s, setup_jit_trace_s, setup_lower_s)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = (setup_infer_shapes_s, setup_jit_trace_s, setup_lower_s,
           setup_compile_or_load_s, setup_cache_hit_pct,
           setup_first_execute_s)
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
# a record rounds its start to 1 us and its length to 1 us
ROUNDING_MS = 0.01

# A whole job in a process of its own (the `import` span is the
# process's first, and other tests clear the list): a small
# mixed-precision program built, the startup program and two steps run.
_JOB = """
import json, sys
sys.path.insert(0, %r)
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.observability import tracing

inferred = []
infer = fluid.framework.Block._infer_op_shapes
fluid.framework.Block._infer_op_shapes = \
    lambda self, op: inferred.append(op.type) or infer(self, op)
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = layers.data(name="x", shape=[8], dtype="float32")
    loss = layers.mean(layers.fc(layers.fc(x, 16, act="relu"), 1))
    mixed_precision.decorate(
        fluid.optimizer.Adam(learning_rate=0.01)).minimize(loss)
built = tracing.build_totals()
exe = fluid.Executor(fluid.CPUPlace())
exe.run(startup)
feed = {"x": np.ones((4, 8), np.float32)}
exe.run(main, feed=feed, fetch_list=[loss])
first = tracing.setup_spans()
exe.run(main, feed=feed, fetch_list=[loss])
print(json.dumps({
    "inferred": inferred, "built": built, "first": first,
    "second": tracing.setup_spans(),
    "programs": [startup.fingerprint[0], main.fingerprint[0]],
    "compiles": tracing.compile_totals()}))
""" % REPO


@pytest.fixture(scope="module")
def job():
    proc = subprocess.run(
        [sys.executable, "-c", _JOB], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _end(span):
    return span["t0"] * 1e3 + span["dur_ms"]


def _inside(child, parent):
    return (child["t0"] * 1e3 >= parent["t0"] * 1e3 - ROUNDING_MS
            and _end(child) <= _end(parent) + ROUNDING_MS)


def test_the_list_of_a_job(job):
    """`import` first, the three build passes, then a `cold_run` a cold
    call with its `trace_step` and `first_dispatch` inside."""
    spans = job["first"]
    names = [s["name"] for s in spans]
    assert names[0] == "import" and names.count("import") == 1
    for name in ("program_build.backward", "program_build.optimize",
                 "program_build.amp"):
        assert names.count(name) == 1, names
    for name in ("cold_run", "trace_step", "trace_step.op_walk",
                 "first_dispatch"):
        assert names.count(name) == 2, names      # startup, main
    assert all(s["kind"] == "setup" and s["dur_ms"] >= 0 for s in spans)
    # the mixed-precision rewrite holds the backward pass, not the
    # optimizer's
    amp, = _named(spans, "program_build.amp")
    backward, = _named(spans, "program_build.backward")
    optimize, = _named(spans, "program_build.optimize")
    assert backward["parent"] == amp["span"] and _inside(backward, amp)
    assert optimize["parent"] is None
    assert optimize["t0"] >= amp["t0"]


def test_a_cold_run_holds_its_trace_and_first_dispatch(job):
    spans = job["first"]
    colds = _named(spans, "cold_run")
    assert [c["ann"]["program"] for c in colds] == job["programs"]
    for cold in colds:
        inside = [s for s in spans if s["parent"] == cold["span"]]
        assert [s["name"] for s in inside] == ["trace_step",
                                               "first_dispatch"]
        for s in inside:
            assert _inside(s, cold)
            assert s["ann"]["program"] == cold["ann"]["program"]
        # its self time is a number: validation, feed normalisation,
        # the first-dispatch commit
        assert cold["dur_ms"] + ROUNDING_MS >= sum(
            s["dur_ms"] for s in inside)
        walk, = [s for s in spans if s["parent"] == inside[0]["span"]]
        assert walk["name"] == "trace_step.op_walk"
        assert walk["ann"]["program"] == cold["ann"]["program"]


def test_first_dispatch_children(job):
    """What JAX timed inside a first dispatch: a jit trace, a lowering
    and a compile or a cache load, each inside the parent, one after
    the other, together no longer than it."""
    spans = job["first"]
    for parent in _named(spans, "first_dispatch"):
        kids = [s for s in spans if s["parent"] == parent["span"]]
        names = [k["name"] for k in kids]
        assert names[:2] == ["first_dispatch.jit_trace",
                             "first_dispatch.lower"], names
        assert names[2] in ("first_dispatch.compile",
                            "first_dispatch.cache_load") and len(kids) == 3
        for k in kids:
            assert _inside(k, parent)
            assert k["ann"]["program"] == parent["ann"]["program"]
        for a, b in zip(kids, kids[1:]):
            assert _end(a) <= b["t0"] * 1e3 + ROUNDING_MS
        assert sum(k["dur_ms"] for k in kids) <= \
            parent["dur_ms"] + ROUNDING_MS
    inside = job["compiles"]["first_dispatch"]
    assert inside["jit_trace_s"] == pytest.approx(
        sum(s["dur_ms"] for s in _named(
            spans, "first_dispatch.jit_trace")) / 1e3, abs=1e-3)


def test_a_second_run_adds_no_span(job):
    assert job["second"] == job["first"]


def test_build_totals_count_an_appended_op(job):
    built, inferred = job["built"], job["inferred"]
    assert built["calls"] == len(inferred) > 0
    assert built["seconds"] > 0
    assert {"mul", "mean", "fill_constant"} <= set(built["by_op"])
    assert {t: r["calls"] for t, r in built["by_op"].items()} == {
        t: inferred.count(t) for t in set(inferred)}


@pytest.fixture
def totals():
    """The list and the totals empty, and put back."""
    spans = tracing.setup_spans()
    tracing.clear_setup_spans()
    tracing.reset_setup_totals()
    yield
    tracing.clear_setup_spans()
    tracing._SETUP.extend(spans)
    tracing.reset_setup_totals()


def test_a_duration_inside_a_first_dispatch_is_its_child(totals):
    with tracing.setup_span("first_dispatch", program=7) as parent:
        time.sleep(0.03)
        jax.monitoring.record_event_duration_secs(TRACE, 0.01,
                                                  fun_name="step1")
        time.sleep(0.25)
        arrival = time.time()
        jax.monitoring.record_event_duration_secs(LOWER, 0.2)
    child, lower, span = tracing.setup_spans()
    assert span["name"] == "first_dispatch" and span["span"] == parent.sid
    assert child["name"] == "first_dispatch.jit_trace"
    assert child["parent"] == lower["parent"] == span["span"]
    assert child["ann"] == {"program": 7, "fun": "step1"}
    assert child["dur_ms"] == pytest.approx(10.0, abs=ROUNDING_MS)
    assert _inside(child, span) and _inside(lower, span)
    # the listener is told no start: it is the arrival less the duration
    assert lower["t0"] == pytest.approx(arrival - 0.2, abs=0.05)
    totals_ = tracing.compile_totals()
    assert totals_["first_dispatch"]["jit_trace_s"] == pytest.approx(0.01)
    assert totals_["first_dispatch"]["lower_s"] == pytest.approx(0.2)
    assert not any(totals_["outside"].values())


def test_only_the_outermost_event_counts(totals):
    """A jitted function traced inside the step's trace, an eager helper
    compiled inside it: events inside a later one's interval."""
    with tracing.setup_span("first_dispatch", program=7):
        start = time.perf_counter()
        time.sleep(0.01)
        jax.monitoring.record_event_duration_secs(TRACE, 0.004)
        jax.monitoring.record_event_duration_secs(COMPILE, 0.002)
        time.sleep(0.01)
        outer = time.perf_counter() - start
        jax.monitoring.record_event_duration_secs(TRACE, outer)
    kids = tracing.setup_spans()[:-1]
    assert [(k["name"], k["dur_ms"]) for k in kids] == [
        ("first_dispatch.jit_trace",
         pytest.approx(outer * 1e3, abs=ROUNDING_MS))]
    inside = tracing.compile_totals()["first_dispatch"]
    assert inside["jit_trace_s"] == pytest.approx(outer)
    assert inside["compile_s"] == pytest.approx(0.0, abs=1e-12)


def test_a_child_never_leaves_its_parent(totals):
    """JAX times with another clock: a duration longer than the parent
    has been open is held to the parent."""
    with tracing.setup_span("first_dispatch", program=7):
        jax.monitoring.record_event_duration_secs(COMPILE, 5.0)
    child, span = tracing.setup_spans()
    assert child["name"] == "first_dispatch.compile"
    assert _inside(child, span) and child["dur_ms"] <= span["dur_ms"]


def test_a_duration_with_no_first_dispatch_open_is_outside(totals):
    jax.monitoring.record_event_duration_secs(TRACE, 0.5)
    with tracing.setup_span("trace_step", program=7):
        jax.monitoring.record_event_duration_secs(COMPILE, 0.25)
    totals_ = tracing.compile_totals()
    assert totals_["outside"]["jit_trace_s"] == pytest.approx(0.5)
    assert totals_["outside"]["compile_s"] == pytest.approx(0.25)
    assert not any(totals_["first_dispatch"].values())
    assert [s["name"] for s in tracing.setup_spans()] == ["trace_step"]


def test_cache_events_are_counted_where_they_arrive(totals):
    """A hit: its retrieval, then the backend-compile interval around
    it, which becomes a `cache_load`; a miss: the interval alone."""
    hits0, misses0 = tracing.compile_cache_events()
    with tracing.setup_span("first_dispatch", program=7):
        time.sleep(0.02)
        jax.monitoring.record_event(HIT)
        jax.monitoring.record_event_duration_secs(RETRIEVAL, 0.004)
        jax.monitoring.record_event_duration_secs(COMPILE, 0.005)
        time.sleep(0.02)
        jax.monitoring.record_event_duration_secs(COMPILE, 0.015)
        jax.monitoring.record_event(MISS)
    jax.monitoring.record_event(MISS)
    jax.monitoring.record_event(MISS)
    names = [s["name"] for s in tracing.setup_spans()]
    assert names == ["first_dispatch.cache_load",
                     "first_dispatch.compile", "first_dispatch"]
    totals_ = tracing.compile_totals()
    inside, outside = totals_["first_dispatch"], totals_["outside"]
    assert (inside["cache_hits"], inside["cache_misses"]) == (1, 1)
    assert (outside["cache_hits"], outside["cache_misses"]) == (0, 2)
    assert inside["cache_load_s"] == pytest.approx(0.005)
    assert inside["cache_retrieval_s"] == pytest.approx(0.004)
    assert inside["compile_s"] == pytest.approx(0.015)
    assert tracing.compile_cache_events() == (hits0 + 1, misses0 + 3)
    assert setup_cache_hit_pct.read({}) == pytest.approx(50.0)
    assert setup_compile_or_load_s.read({}) == pytest.approx(
        0.020, abs=1e-4)


def test_one_listener_each():
    tracing.compile_totals()
    tracing.compile_totals()
    with tracing.setup_span("trace_step"):
        pass
    from jax._src import monitoring
    for listeners in (monitoring.get_event_listeners(),
                      monitoring.get_event_duration_listeners()):
        ours = [cb for cb in listeners
                if getattr(cb, "__module__", "") == tracing.__name__]
        assert len(ours) == 1, listeners


def test_the_readers_after_a_run_and_after_a_reset(totals):
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, size=2))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])
    read = {m.__name__.rsplit(".", 1)[1]: m.read({}) for m in READERS}
    hit_pct = read.pop("setup_cache_hit_pct")
    # None when no compile request of a first dispatch was answered or
    # written by the persistent cache (a compile under JAX's thresholds)
    assert hit_pct is None or 0.0 <= hit_pct <= 100.0
    assert all(isinstance(v, float) and v >= 0.0 for v in read.values()), \
        read
    first = sum(s["dur_ms"] for s in _named(tracing.setup_spans(),
                                            "first_dispatch")) / 1e3
    assert (read["setup_jit_trace_s"] + read["setup_lower_s"]
            + read["setup_compile_or_load_s"]
            + read["setup_first_execute_s"]) == pytest.approx(first)
    tracing.clear_setup_spans()
    tracing.reset_setup_totals()
    assert [m.read({}) for m in READERS] == [None] * len(READERS)
