"""The program's own spans (docs/TRACING.md, "Profiler spans"): one
`pt.step` per `Executor.run` with the phase spans inside it, in any open
profiler session and with no switch; the flight/telemetry record built
from the same stamps; set-up spans kept once per executable; a slow step
reaching the flight recorder of an ordinary run.
"""
import glob
import os
import tempfile
import time

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers, profiler
from paddle_tpu.core.engine import Engine, _SlowSteps
from paddle_tpu.core.scope import Scope
from paddle_tpu.observability import metrics, recorder, tracing

# what a steady step emits, in order (pt.engine.trace is the cold path's)
STEADY = ["pt.executor.feed", "pt.engine.feed", "pt.engine.args",
          "pt.engine.rng", "pt.engine.dispatch", "pt.engine.release",
          "pt.engine.writeback", "pt.engine.fetch"]


def _tiny():
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
    return exe, main, scope, {"x": np.ones((2, 4), np.float32)}, loss


@pytest.fixture
def quiet():
    """Every gate off, as an ordinary run has them."""
    saved = (metrics._TELEMETRY[0], recorder._ENABLED[0],
             recorder._FAULT[0], recorder._WATCHDOG[0])
    metrics._TELEMETRY[0] = False
    recorder._ENABLED[0] = recorder._FAULT[0] = False
    recorder._WATCHDOG[0] = False
    metrics._recompute_hot()
    yield
    (metrics._TELEMETRY[0], recorder._ENABLED[0], recorder._FAULT[0],
     recorder._WATCHDOG[0]) = saved
    metrics._recompute_hot()


def _pt_spans(trace_dir, also=()):
    """{thread line: [(name, start_ns, end_ns)]} of the `pt.` spans (and
    of the events whose names hold one of `also`)."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events if e.name.startswith("pt.")
                     or any(part in e.name for part in also)]
            if spans:
                out[f"{plane.name}/{line.name}"] = sorted(
                    spans, key=lambda s: s[1])
    return out


def test_steps_and_phases_in_a_profiler_session(quiet):
    """Under a session opened by anyone (here jax.profiler itself, as the
    benchmark does): three `pt.step` spans on one host thread, each
    holding the phase spans in order, children inside the parent, no
    sibling overlapping the next."""
    exe, main, scope, feed, loss = _tiny()
    with fluid.scope_guard(scope):
        exe.run(main, feed=feed, fetch_list=[loss])     # cold: traces
        d = tempfile.mkdtemp(prefix="pt_spans_")
        jax.profiler.start_trace(d)
        try:
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
        finally:
            jax.profiler.stop_trace()
    by_line = _pt_spans(d)
    assert len(by_line) == 1, list(by_line)
    spans, = by_line.values()
    steps = [s for s in spans if s[0] == "pt.step"]
    assert len(steps) == 3
    for (_, a, b), nxt in zip(steps, steps[1:] + [None]):
        inside = [s for s in spans
                  if s[0] != "pt.step" and a <= s[1] and s[2] <= b]
        assert [s[0] for s in inside] == STEADY
        for (_, _, end), (_, start, _) in zip(inside, inside[1:]):
            assert end <= start             # siblings do not overlap
        if nxt is not None:
            assert b <= nxt[1]
    # every phase span lies in some step
    assert len(spans) == 3 * (1 + len(STEADY))


def test_a_steady_step_is_one_executable_call(quiet):
    """The rng state is split INSIDE the compiled step: between a
    steady `pt.step`'s start and end the host calls one executable,
    the step's own (no `_threefry_split`, no `_unstack`), dropout or
    not."""
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.dropout(layers.fc(x, size=2), 0.5))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = Scope()
    feed = {"x": np.ones((2, 4), np.float32)}
    d = tempfile.mkdtemp(prefix="pt_spans_")
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])     # cold: traces
        jax.profiler.start_trace(d)
        try:
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
        finally:
            jax.profiler.stop_trace()
    spans, = _pt_spans(d, also=("PjitFunction(",
                                "Executable::Execute")).values()
    steps = [s for s in spans if s[0] == "pt.step"]
    assert len(steps) == 3
    for _, a, b in steps:
        inside = [s[0] for s in spans if a <= s[1] and s[2] <= b]
        calls = {n for n in inside if n.startswith("PjitFunction(")}
        assert calls == {"PjitFunction(step1)"}, calls
        runs = [n for n in inside if n.endswith("Executable::Execute")]
        assert len(runs) == 1, runs


def test_cold_step_spans_trace_and_first_dispatch(quiet):
    exe, main, scope, feed, loss = _tiny()
    d = tempfile.mkdtemp(prefix="pt_spans_")
    jax.profiler.start_trace(d)
    try:
        with fluid.scope_guard(scope):
            exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        jax.profiler.stop_trace()
    spans, = _pt_spans(d).values()
    names = [s[0] for s in spans]
    # the set-up spans are in the session too, as `pt.setup.<name>`:
    # `cold_run` from where the call is known to be cold to its end
    assert names == ["pt.step", "pt.executor.feed", "pt.engine.feed",
                     "pt.setup.cold_run", "pt.engine.trace",
                     "pt.setup.trace_step", "pt.setup.trace_step.op_walk",
                     "pt.engine.args", "pt.engine.rng",
                     "pt.setup.first_dispatch",
                     "pt.engine.first_dispatch", "pt.engine.release",
                     "pt.engine.writeback", "pt.engine.fetch"]


def test_record_is_built_from_the_stamps(quiet):
    """Telemetry on: the flight record holds the new phase keys and each
    phase's offset from the step's start, and finish_step's phase spans
    start where the stamps say — not laid end to end."""
    exe, main, scope, feed, loss = _tiny()
    with fluid.scope_guard(scope):
        exe.run(main, feed=feed, fetch_list=[loss])
        metrics.enable_telemetry(True)
        tracing.clear_spans()
        exe.run(main, feed=feed, fetch_list=[loss])
    rec = recorder.flight_recorder().snapshot()[-1]
    keys = [n.replace("pt.engine.", "").replace("pt.executor.", "executor_")
            + "_ms" for n in STEADY]
    assert keys == ["executor_feed_ms", "feed_ms", "args_ms", "rng_ms",
                    "dispatch_ms", "release_ms", "writeback_ms",
                    "fetch_ms"]
    assert tuple(k for k in recorder.PHASE_KEYS if k != "trace_ms") \
        == tuple(keys)
    assert len(profiler.PHASE_NAMES) == len(recorder.PHASE_KEYS)
    for k in keys:
        assert rec["phases"][k] > 0.0
        assert rec["phase_t0_ms"][k] >= 0.0
    assert rec["fast_path"] and not rec["traced"] and "sig" in rec
    assert "slow" not in rec
    starts = [rec["phase_t0_ms"][k] for k in keys]
    assert starts == sorted(starts)
    # phases leave gaps between them (Python outside any phase), so the
    # real offsets run ahead of the stacked durations
    stacked = sum(rec["phases"][k] for k in keys[:-1])
    assert rec["phase_t0_ms"]["fetch_ms"] > stacked
    assert rec["phases"]["total_ms"] >= (
        rec["phase_t0_ms"]["fetch_ms"] + rec["phases"]["fetch_ms"])
    by_name = {s["name"]: s for s in tracing.spans_snapshot()
               if s["kind"] in ("step", "phase")}
    t0 = by_name["step"]["t0"]
    for k in keys:
        span = by_name[k[:-3]]
        assert span["t0"] == pytest.approx(
            t0 + rec["phase_t0_ms"][k] / 1e3, abs=2e-6)


def test_everything_off_builds_no_record(quiet, monkeypatch):
    """No switch set: a step builds no record (the one builder is never
    entered), appends nothing to the span ring or the flight ring, and
    the set-up list holds one `trace_step` and one `first_dispatch` per
    program run — none added by later steps."""
    built = []
    real = profiler.StepClock.phases
    monkeypatch.setattr(profiler.StepClock, "phases",
                        lambda self: built.append(1) or real(self))
    tracing.clear_setup_spans()
    exe, main, scope, feed, loss = _tiny()     # runs the startup program
    ring0 = tracing.span_buffer().total_appended
    flight0 = recorder.flight_recorder().total_appended
    with fluid.scope_guard(scope):
        for _ in range(5):
            exe.run(main, feed=feed, fetch_list=[loss])
    assert not built
    assert tracing.span_buffer().total_appended == ring0
    assert recorder.flight_recorder().total_appended == flight0
    # (the list's other names, `cold_run`, `program_build.*` and
    # `first_dispatch`'s children: tests/test_setup_spans.py)
    names = [s["name"] for s in tracing.setup_spans()
             if s["name"] in ("trace_step", "trace_step.op_walk",
                              "first_dispatch")]
    assert sorted(names) == sorted(
        2 * ["trace_step", "trace_step.op_walk", "first_dispatch"])
    for s in tracing.setup_spans():
        assert s["kind"] == "setup" and s["dur_ms"] >= 0.0
    walks = [s for s in tracing.setup_spans()
             if s["name"] == "trace_step.op_walk"]
    parents = {s["span"] for s in tracing.setup_spans()
               if s["name"] == "trace_step"}
    assert {w["parent"] for w in walks} == parents


def test_setup_spans_reach_the_ring_when_hot(quiet):
    metrics.enable_telemetry(True)
    tracing.clear_spans()
    _tiny()
    kinds = [s["name"] for s in tracing.spans_snapshot()
             if s["kind"] == "setup"]
    assert "trace_step" in kinds and "first_dispatch" in kinds


class _SleepyFeed(dict):
    """A feed whose conversion takes `seconds` once armed."""
    seconds = 0.0

    def items(self):
        if self.seconds:
            time.sleep(self.seconds)
        return super().items()


def test_slow_step_reaches_the_flight_ring_without_a_switch(quiet):
    """After the history exists, a step made slow on purpose lands in
    the flight ring with "slow": true, its phases and the collection
    counts; its neighbours do not."""
    exe, main, scope, feed, loss = _tiny()
    feed = _SleepyFeed(feed)
    fr = recorder.flight_recorder()
    with fluid.scope_guard(scope):
        for _ in range(2 * _SlowSteps.REFRESH):
            exe.run(main, feed=feed, fetch_list=[loss])
        before = fr.total_appended
        feed.seconds = 0.25
        exe.run(main, feed=feed, fetch_list=[loss])
        feed.seconds = 0.0
        assert fr.total_appended == before + 1
        rec = fr.snapshot()[-1]
        exe.run(main, feed=feed, fetch_list=[loss])
    assert rec["slow"] is True
    assert rec["phases"]["executor_feed_ms"] >= 250.0
    assert rec["phases"]["total_ms"] > 3 * rec["median_ms"]
    assert len(rec["gc"]["collections"]) == 3
    assert 1 <= rec["gc"]["over_steps"] <= _SlowSteps.REFRESH
    # the step after it was not slow (a quick step on a loaded machine
    # may still be: at most that one more record)
    assert fr.total_appended <= before + 2
    d = tempfile.mkdtemp(prefix="pt_slow_")
    fr.dump("unit_slow", directory=d)
    summ, = recorder.summarize_dumps(d)
    assert any(s["step"] == rec["step"] for s in summ["slow_steps"])


def test_first_steps_are_never_judged():
    slow = _SlowSteps()
    # a first, compiling step and fifteen quick ones: no history yet
    assert not slow.is_slow(10_000_000_000)
    assert not any(slow.is_slow(1_000_000)
                   for _ in range(_SlowSteps.MIN_HISTORY - 1))
    assert slow.median_ns == 1_000_000
    assert not slow.is_slow(2_900_000)
    assert slow.is_slow(3_100_000)
    delta = slow.gc_delta()
    assert delta["over_steps"] == 2 and len(delta["collections"]) == 3


def test_engine_run_alone_is_a_step(quiet):
    """Engine.run without an Executor around it (the data-parallel
    engine, tests, tools) begins its own step on the clock."""
    exe, main, scope, feed, loss = _tiny()
    eng = Engine()
    metrics.enable_telemetry(True)
    with fluid.scope_guard(scope):
        eng.run(main, scope, None, feed, [loss.name])
        eng.run(main, scope, None, feed, [loss.name])
    rec = recorder.flight_recorder().snapshot()[-1]
    assert "executor_feed_ms" not in rec["phases"]
    assert rec["phase_t0_ms"]["feed_ms"] < 1.0
    assert rec["step"] == 2


def test_record_event_needs_no_session_of_ours(quiet):
    """RecordEvent opens its TraceAnnotation whoever opened the session."""
    d = tempfile.mkdtemp(prefix="pt_spans_")
    jax.profiler.start_trace(d)
    try:
        with profiler.RecordEvent("pt.test.record_event"):
            pass
    finally:
        jax.profiler.stop_trace()
    spans, = _pt_spans(d).values()
    assert [s[0] for s in spans] == ["pt.test.record_event"]
