"""Observability subsystem (docs/OBSERVABILITY.md): metrics registry
(counters/gauges/histograms + Prometheus exposition), the step flight
recorder (ring buffer, dump-on-fault postmortems), the one-boolean
hot-path gate, the scrape endpoint, and the fleet-report tooling."""
import json
import math
import os
import subprocess
import sys
import tempfile
import textwrap
import threading
import unittest

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.core.flags import get_flags, set_flags  # noqa: E402
from paddle_tpu.distributed import async_ps, faults  # noqa: E402
from paddle_tpu.distributed.faults import FaultPlan  # noqa: E402
from paddle_tpu.observability import (  # noqa: E402
    export, metrics, recorder)
from paddle_tpu.observability.metrics import (  # noqa: E402
    Counter, EngineCounters, Gauge, Histogram, MetricsRegistry,
    exponential_buckets)


def _quiet_gates(test):
    """Force every recorder/telemetry gate off for a test, restoring
    the prior state after (other tests may have armed the watchdog or
    a fault plan for the life of the process)."""
    prev = (metrics._TELEMETRY[0], recorder._ENABLED[0],
            recorder._FAULT[0], recorder._WATCHDOG[0])

    def restore():
        metrics._TELEMETRY[0] = prev[0]
        recorder._ENABLED[0] = prev[1]
        recorder._FAULT[0] = prev[2]
        recorder._WATCHDOG[0] = prev[3]
        metrics._recompute_hot()

    test.addCleanup(restore)
    metrics.enable_telemetry(False)
    recorder.enable(False)
    recorder.set_fault_active(False)
    recorder.set_watchdog_active(False)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

class TestHistogram(unittest.TestCase):
    def test_exponential_buckets_shape(self):
        b = exponential_buckets(0.001, 2.0, 4)
        np.testing.assert_allclose(b, [0.001, 0.002, 0.004, 0.008])
        with self.assertRaises(ValueError):
            exponential_buckets(0.0, 2.0, 4)

    def test_bucketing_is_cumulative_and_exact(self):
        h = Histogram("h", buckets=[0.5, 2.0, 8.0])
        for v in (0.25, 0.25, 1.0, 4.0, 50.0):
            h.observe(v)
        # cumulative counts per upper bound, +Inf last
        self.assertEqual(h.cumulative(),
                         [(0.5, 2), (2.0, 3), (8.0, 4),
                          (math.inf, 5)])
        self.assertEqual(h.count, 5)
        self.assertEqual(h.sum, 55.5)

    def test_boundary_lands_in_its_bucket(self):
        # le is inclusive (Prometheus semantics)
        h = Histogram("h", buckets=[1.0, 2.0])
        h.observe(1.0)
        self.assertEqual(h.cumulative()[0], (1.0, 1))

    def test_reset(self):
        h = Histogram("h", buckets=[1.0])
        h.observe(0.5)
        h.reset()
        self.assertEqual((h.count, h.sum), (0, 0.0))
        self.assertEqual(h.cumulative(), [(1.0, 0), (math.inf, 0)])


class TestRegistry(unittest.TestCase):
    def test_register_dedupes_by_name(self):
        r = MetricsRegistry()
        a = r.register(Counter("c"))
        b = r.register(Counter("c"))
        self.assertIs(a, b)

    def test_collector_exception_does_not_break_collect(self):
        r = MetricsRegistry()
        r.counter("ok").inc()

        def bad():
            raise RuntimeError("boom")
        r.register_collector(bad)
        fams = {f.name for f in r.collect()}
        self.assertIn("ok", fams)

    def test_engine_counters_snapshot_and_reset(self):
        c = EngineCounters({"runs": 0, "traces": 0,
                            "comm_overlap_frac": 0.0})
        c["runs"] += 3
        c["comm_overlap_frac"] = 0.75
        snap = c.snapshot()
        self.assertEqual(snap["runs"], 3)
        c["runs"] += 1
        self.assertEqual(snap["runs"], 3)       # stable copy
        pre = c.reset(["runs"])
        self.assertEqual(pre["runs"], 4)
        self.assertEqual(c["runs"], 0)
        self.assertEqual(c["comm_overlap_frac"], 0.75)
        c.reset()
        self.assertEqual(c["comm_overlap_frac"], 0.0)
        self.assertIsInstance(c["comm_overlap_frac"], float)
        self.assertIsInstance(c["runs"], int)   # types preserved
        # dict-style read path (every existing caller) still works
        self.assertIsInstance(c, dict)
        self.assertEqual(sorted(c), ["comm_overlap_frac", "runs",
                                     "traces"])


# ---------------------------------------------------------------------------
# exposition format
# ---------------------------------------------------------------------------

class TestExposition(unittest.TestCase):
    def test_golden_output(self):
        r = MetricsRegistry()
        r.counter("pt_test_total", help="things done").inc(2)
        g = r.gauge("pt_test_depth")
        g.set(1.5)
        g.set(3, kind="b")
        h = r.histogram("pt_test_seconds", buckets=[0.5, 2.0])
        h.observe(0.25)
        h.observe(0.75)
        text = export.render_exposition(r)
        expected = textwrap.dedent("""\
            # HELP pt_test_total things done
            # TYPE pt_test_total counter
            pt_test_total 2
            # TYPE pt_test_depth gauge
            pt_test_depth 1.5
            pt_test_depth{kind="b"} 3
            # TYPE pt_test_seconds histogram
            pt_test_seconds_bucket{le="0.5"} 1
            pt_test_seconds_bucket{le="2"} 2
            pt_test_seconds_bucket{le="+Inf"} 2
            pt_test_seconds_sum 1
            pt_test_seconds_count 2
            """)
        self.assertEqual(text, expected)

    def test_label_escaping(self):
        r = MetricsRegistry()
        r.gauge("g").set(1, ep='a"b\\c\nd')
        text = export.render_exposition(r)
        self.assertIn(r'g{ep="a\"b\\c\nd"} 1', text)

    def test_default_registry_serves_required_families(self):
        # the catalog metrics_report gates on must all pre-exist (a
        # trainer that never checkpointed still exposes
        # pt_ckpt_save_seconds with count 0)
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import metrics_report
        snap = export.metrics_snapshot()
        self.assertEqual(metrics_report.missing_families(snap), [])

    def test_snapshot_roundtrips_through_json(self):
        snap = export.metrics_snapshot()
        self.assertEqual(json.loads(json.dumps(snap)), snap)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder(unittest.TestCase):
    def test_ring_wraparound_keeps_newest(self):
        fr = recorder.FlightRecorder(capacity=4)
        for i in range(10):
            fr.append({"step": i, "phases": {"total_ms": float(i)}})
        snap = fr.snapshot()
        self.assertEqual([r["step"] for r in snap], [6, 7, 8, 9])
        self.assertEqual(fr.total_appended, 10)
        self.assertEqual(len(fr), 4)

    def test_dump_and_read(self):
        d = tempfile.mkdtemp()
        fr = recorder.FlightRecorder(capacity=8)
        for i in range(3):
            fr.append({"step": i,
                       "phases": {"feed_ms": 0.1, "total_ms": 1.0}})
        path = fr.dump("unit_test", directory=d,
                       extra={"note": "hello"})
        self.assertTrue(os.path.exists(path))
        data = recorder.read_dump(path)
        self.assertEqual(data["header"]["reason"], "unit_test")
        self.assertEqual(data["header"]["note"], "hello")
        self.assertEqual(len(data["records"]), 3)
        summ = recorder.summarize_dumps(d)
        self.assertEqual(summ[0]["reason"], "unit_test")
        self.assertEqual(summ[0]["steps_retained"], 3)
        self.assertEqual(summ[0]["mean_phase_ms"]["total_ms"], 1.0)

    def test_empty_ring_dump_returns_none(self):
        d = tempfile.mkdtemp()
        fr = recorder.FlightRecorder(capacity=4)
        self.assertIsNone(fr.dump("empty", directory=d))
        self.assertEqual(os.listdir(d), [])

    def test_record_step_gated_off_when_quiet(self):
        _quiet_gates(self)
        fr = recorder.flight_recorder()
        before = fr.total_appended
        recorder.record_step({"step": 1, "phases": {"total_ms": 1.0}})
        self.assertEqual(fr.total_appended, before)
        self.assertFalse(recorder.recording_active())


# ---------------------------------------------------------------------------
# hot-path gate
# ---------------------------------------------------------------------------

class TestHotPathGate(unittest.TestCase):
    def _tiny_engine(self):
        import paddle_tpu as fluid
        from paddle_tpu import layers
        from paddle_tpu.core.engine import Engine
        from paddle_tpu.core.scope import Scope
        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.fc(x, size=2)
            loss = layers.mean(y)
        scope = Scope()
        with fluid.scope_guard(scope):
            fluid.Executor().run(startup)
        feed = {"x": np.ones((2, 4), np.float32)}
        return fluid, Engine(), main, scope, feed, [loss.name]

    def test_disabled_path_does_no_observability_work(self):
        _quiet_gates(self)
        from paddle_tpu import profiler
        fluid, eng, prog, scope, feed, fetch = self._tiny_engine()
        self.assertFalse(metrics._HOT[0])
        self.assertFalse(profiler.profiling_active())
        fr = recorder.flight_recorder()
        before = fr.total_appended
        h = metrics.histogram("pt_step_total_seconds")
        count0 = h.count
        with fluid.scope_guard(scope):
            for _ in range(3):
                eng.run(prog, scope, None, feed, fetch)
        # no ring appends, no histogram observations: the single
        # boolean kept the entire instrumentation branch cold
        self.assertEqual(fr.total_appended, before)
        self.assertEqual(h.count, count0)

    def test_enabled_path_records_phases(self):
        _quiet_gates(self)
        fluid, eng, prog, scope, feed, fetch = self._tiny_engine()
        metrics.enable_telemetry(True)
        self.assertTrue(metrics._HOT[0])
        fr = recorder.flight_recorder()
        before = fr.total_appended
        h = metrics.histogram("pt_step_total_seconds")
        count0 = h.count
        with fluid.scope_guard(scope):
            for _ in range(3):
                eng.run(prog, scope, None, feed, fetch)
        self.assertEqual(fr.total_appended, before + 3)
        self.assertEqual(h.count, count0 + 3)
        rec = fr.snapshot()[-1]
        for k in ("feed_ms", "dispatch_ms", "fetch_ms", "total_ms"):
            self.assertIn(k, rec["phases"])
        self.assertIn("sig", rec)
        self.assertTrue(rec["fast_path"])   # steady state by run 3

    def test_telemetry_flag_toggles_gate(self):
        _quiet_gates(self)
        old = get_flags(["FLAGS_telemetry"])
        self.addCleanup(set_flags, old)
        set_flags({"FLAGS_telemetry": True})
        self.assertTrue(metrics.telemetry_active())
        set_flags({"FLAGS_telemetry": False})
        self.assertFalse(metrics.telemetry_active())

    def test_fault_install_arms_recorder(self):
        _quiet_gates(self)
        with faults.scoped(FaultPlan(seed=1)):
            self.assertTrue(recorder.recording_active())
            self.assertTrue(metrics._HOT[0])
        self.assertFalse(recorder.recording_active())


# ---------------------------------------------------------------------------
# dump on injected fault (subprocess: the PT_FAULT_PLAN postmortem)
# ---------------------------------------------------------------------------

class TestDumpOnFault(unittest.TestCase):
    def test_injected_kill_dumps_flight_with_phase_timings(self):
        d = tempfile.mkdtemp()
        script = os.path.join(d, "victim.py")
        with open(script, "w") as f:
            f.write(textwrap.dedent(f"""
                import os, sys
                os.environ.setdefault("JAX_PLATFORMS", "cpu")
                os.environ.pop("XLA_FLAGS", None)
                sys.path.insert(0, {REPO!r})
                import numpy as np
                import paddle_tpu as fluid
                from paddle_tpu import layers
                from paddle_tpu.core.engine import Engine
                from paddle_tpu.core.scope import Scope

                main, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main, startup):
                    x = layers.data(name="x", shape=[4],
                                    dtype="float32")
                    loss = layers.mean(layers.fc(x, size=2))
                scope = Scope()
                with fluid.scope_guard(scope):
                    fluid.Executor().run(startup)
                    eng = Engine()
                    feed = {{"x": np.ones((2, 4), np.float32)}}
                    for _ in range(10):
                        eng.run(main, scope, None, feed, [loss.name])
                sys.exit(7)   # must never get here
            """))
        env = dict(os.environ, PT_FAULT_PLAN="kill_at_step=3",
                   PT_FLIGHT_DIR=d)
        env.pop("XLA_FLAGS", None)
        r = subprocess.run([sys.executable, script], env=env,
                           capture_output=True, text=True, timeout=180,
                           cwd=REPO)
        self.assertEqual(r.returncode, faults.KILL_EXIT_CODE,
                         r.stdout + r.stderr)

        dumps = recorder.find_dumps(d)
        self.assertEqual(len(dumps), 1)
        data = recorder.read_dump(dumps[0])
        self.assertEqual(data["header"]["reason"], "injected_fault")
        self.assertEqual(data["header"]["killed_at"], 3)
        # the postmortem carries per-step phase timings for the steps
        # before the kill (the fault check precedes run 3's record;
        # steps are per-engine run counts, and the startup Executor's
        # own engine contributes its run too — the ring is
        # process-wide)
        self.assertEqual([rec["step"] for rec in data["records"]][-2:],
                         [1, 2])
        for rec in data["records"]:
            self.assertGreater(rec["phases"]["total_ms"], 0.0)
        self.assertGreaterEqual(
            data["header"]["counters"].get("runs", 0), 3)

        # readable by BOTH report tools (the acceptance criterion)
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import chaos_report
        import metrics_report
        summ = chaos_report.summarize_flight_dumps(d)
        self.assertEqual(summ[0]["reason"], "injected_fault")
        self.assertEqual(summ[0]["last_step"], 2)
        rep = metrics_report.fleet_report(flight_dir=d,
                                          include_local=False)
        self.assertEqual(rep["flight_dumps"][0]["reason"],
                         "injected_fault")


# ---------------------------------------------------------------------------
# scrape endpoints
# ---------------------------------------------------------------------------

class TestMetricsServer(unittest.TestCase):
    def test_live_scrape_text_and_json(self):
        metrics.counter("pt_test_scrape_total").inc(5)
        srv = export.MetricsServer(port=0)
        srv.start()
        self.addCleanup(srv.stop)
        text = export.scrape(srv.endpoint)
        self.assertIn("pt_test_scrape_total 5", text)
        # every standard family is served live
        for fam in ("pt_step_total_seconds", "pt_ckpt_save_seconds",
                    "pt_heartbeats_sent_total"):
            self.assertIn(fam, text)
        snap = export.scrape(srv.endpoint, as_json=True)
        self.assertEqual(snap["pt_test_scrape_total"]["type"],
                         "counter")

    def test_pserver_serves_metrics_natively(self):
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            ep = f"127.0.0.1:{s.getsockname()[1]}"
        metrics.counter("pt_test_ps_total").inc(2)
        values = {"w": np.zeros(2, np.float32)}
        srv = async_ps.AsyncParameterServer(
            ep, fanin=1, get_var=values.__getitem__,
            apply_update=lambda n, v, m: None, known_params=["w"])
        t = threading.Thread(target=srv.serve, daemon=True)
        t.start()
        try:
            text = export.scrape(ep)
            self.assertIn("pt_test_ps_total 2", text)
        finally:
            async_ps.send_complete(ep, 0)
            t.join(timeout=10)
        self.assertFalse(t.is_alive())


# ---------------------------------------------------------------------------
# fleet report tooling
# ---------------------------------------------------------------------------

class TestMetricsReport(unittest.TestCase):
    def setUp(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))

    def test_histogram_merge_sums_buckets(self):
        import metrics_report
        fam = {"type": "histogram", "samples": [
            {"labels": {}, "sum": 1.0, "count": 2,
             "buckets": [[0.1, 1], ["+Inf", 2]]}]}
        merged = metrics_report.merge_snapshots(
            [("t0", {"h": fam}), ("t1", {"h": fam})])
        s = merged["h"]["samples"][0]
        self.assertEqual((s["sum"], s["count"]), (2.0, 4))
        self.assertEqual(s["buckets"], [[0.1, 2], ["+Inf", 4]])

    def test_counter_merge_and_gauge_origin_labels(self):
        import metrics_report
        c = {"type": "counter",
             "samples": [{"labels": {}, "value": 3}]}
        g = {"type": "gauge",
             "samples": [{"labels": {}, "value": 1.0}]}
        merged = metrics_report.merge_snapshots(
            [("t0", {"c": c, "g": g}), ("t1", {"c": c, "g": g})])
        self.assertEqual(merged["c"]["samples"][0]["value"], 6.0)
        origins = {s["labels"]["origin"]
                   for s in merged["g"]["samples"]}
        self.assertEqual(origins, {"t0", "t1"})

    def test_missing_family_gate_fails(self):
        import metrics_report
        d = tempfile.mkdtemp()     # empty: no dumps, no local source
        rc = metrics_report.main(["--flight-dir", d, "--no-local",
                                  "--check-families"])
        self.assertEqual(rc, 1)

    def test_family_gate_passes_with_local_registry(self):
        import metrics_report
        d = tempfile.mkdtemp()
        rc = metrics_report.main(["--flight-dir", d,
                                  "--check-families"])
        self.assertEqual(rc, 0)

    def test_metrics_jsonl_dump_feeds_fleet_report(self):
        import metrics_report
        d = tempfile.mkdtemp()
        metrics.histogram("pt_step_total_seconds").observe(0.01)
        path = export.dump_metrics(directory=d)
        self.assertTrue(path.endswith(f"metrics_{os.getpid()}.jsonl"))
        rep = metrics_report.fleet_report(flight_dir=d,
                                          include_local=False)
        self.assertGreaterEqual(rep["total_steps_observed"], 1)
        self.assertIn("pt_step_total_seconds", rep["families"])


# ---------------------------------------------------------------------------
# profiler satellites: event cap + real thread ids + timeline merge
# ---------------------------------------------------------------------------

class TestProfilerSatellites(unittest.TestCase):
    def _stop(self, profiler):
        d = tempfile.mkdtemp()
        profiler.stop_profiler(
            profile_path=os.path.join(d, "p.chrome_trace.json"))

    def test_event_ring_is_capped(self):
        from paddle_tpu import profiler
        profiler.set_max_events(16)
        self.addCleanup(profiler.set_max_events,
                        profiler._MAX_EVENTS_DEFAULT)
        profiler.start_profiler("CPU")
        try:
            for i in range(100):
                with profiler.RecordEvent(f"ev{i}"):
                    pass
            self.assertLessEqual(len(profiler._events), 16)
            names = [e["name"] for e in profiler._events]
            self.assertEqual(names[-1], "ev99")   # newest retained
        finally:
            self._stop(profiler)

    def test_events_carry_real_thread_id(self):
        from paddle_tpu import profiler
        profiler.start_profiler("CPU")
        try:
            def work(key):
                with profiler.RecordEvent(f"t_{key}"):
                    pass

            work("main")
            t = threading.Thread(target=work, args=("worker",))
            t.start()
            t.join()
            tids = {e["name"]: e["tid"] for e in profiler._events
                    if e["name"].startswith("t_")}
            self.assertNotEqual(tids["t_main"], 0)
            self.assertNotEqual(tids["t_main"], tids["t_worker"])
        finally:
            self._stop(profiler)

    def test_timeline_merges_flight_jsonl(self):
        d = tempfile.mkdtemp()
        fr = recorder.FlightRecorder(capacity=4)
        fr.append({"step": 0, "t_host": 100.0, "fast_path": True,
                   "phases": {"feed_ms": 0.2, "dispatch_ms": 1.0,
                              "fetch_ms": 0.1, "total_ms": 1.3}})
        path = fr.dump("unit_test", directory=d)
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import timeline
        trace = timeline.merge([("dead", path)])
        lanes = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        self.assertEqual(lanes, {"feed", "dispatch", "fetch"})
        self.assertTrue(all(e["pid"] == 0
                            for e in trace["traceEvents"]))


if __name__ == "__main__":
    unittest.main()
