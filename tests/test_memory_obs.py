"""HBM memory observatory (docs/MEMORY.md): owner-attributed
live-buffer census vs hand-built owners, the leak sentinel, the
pressure watermark, OOM postmortem dumps (exactly one per exception),
the one-boolean hot gate, and the timeline/metrics tooling hooks."""
import gc
import os
import sys
import tempfile
import unittest
import warnings

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.core.scope import Scope  # noqa: E402
from paddle_tpu.observability import (  # noqa: E402
    export, memory, metrics, recorder)
from paddle_tpu.stability.ghost import GhostRing  # noqa: E402

NEW_FAMILIES = (
    "pt_hbm_owner_bytes", "pt_hbm_live_bytes",
    "pt_hbm_leak_suspect_bytes",
    "pt_memdumps_total", "pt_oom_postmortems_total")


def _quiet_gates(test):
    """Force every observability gate off for the test, restoring the
    prior state after (the census must not leak into other tests)."""
    prev = (metrics._TELEMETRY[0], recorder._ENABLED[0],
            recorder._FAULT[0], recorder._WATCHDOG[0],
            memory._ENABLED[0])

    def restore():
        metrics._TELEMETRY[0] = prev[0]
        recorder._ENABLED[0] = prev[1]
        recorder._FAULT[0] = prev[2]
        recorder._WATCHDOG[0] = prev[3]
        memory._ENABLED[0] = prev[4]
        metrics._recompute_hot()

    test.addCleanup(restore)
    metrics.enable_telemetry(False)
    recorder.enable(False)
    recorder.set_fault_active(False)
    recorder.set_watchdog_active(False)
    memory.enable(False)


def _device_scope(values):
    """Scope holding device-resident jax arrays, the shape the engine
    leaves persistables in after a step."""
    scope = Scope()
    for name, arr in values.items():
        scope.var(name).set_value(jax.device_put(arr))
    return scope


def _flight_dir(test):
    d = tempfile.mkdtemp(prefix="pt_memdump_test_")
    prev = os.environ.get("PT_FLIGHT_DIR")

    def restore():
        if prev is None:
            os.environ.pop("PT_FLIGHT_DIR", None)
        else:
            os.environ["PT_FLIGHT_DIR"] = prev

    test.addCleanup(restore)
    os.environ["PT_FLIGHT_DIR"] = d
    return d


class _FakeFetchHandle:
    """Stand-in for async_dispatch.FetchHandle: the census reads only
    ``_name`` and ``_value`` and must see an undrained handle's device
    payload, and nothing once drained."""

    def __init__(self, name, value):
        self._name = name
        self._value = value


class TestCensusAccounting(unittest.TestCase):
    def setUp(self):
        memory.reset()
        self.addCleanup(memory.reset)

    def test_scope_owner_bytes_match_hand_built_scope(self):
        vals = {"w": np.ones((32, 16), np.float32),
                "b": np.ones((16,), np.float32)}
        scope = _device_scope(vals)
        memory.track_scope(scope)
        c = memory.census()
        want = sum(v.size * 4 for v in vals.values())
        self.assertEqual(c["owners"]["scope"]["bytes"], want)
        self.assertEqual(c["owners"]["scope"]["count"], 2)
        # the reconciliation never loses bytes: tagged + orphan = live
        self.assertEqual(c["tagged_bytes"] + c["orphan_bytes"],
                         c["live_bytes"])
        self.assertGreaterEqual(c["live_bytes"], want)

    def test_aliased_buffer_counted_once(self):
        a = jax.device_put(np.ones((8, 8), np.float32))
        scope = Scope()
        scope.var("x").set_value(a)
        scope.var("x_alias").set_value(a)
        memory.track_scope(scope)
        c = memory.census()
        self.assertEqual(c["owners"]["scope"]["count"], 1)
        self.assertEqual(c["owners"]["scope"]["bytes"], int(a.nbytes))

    def test_ghost_ring_appears_and_dies_with_the_ring(self):
        scope = _device_scope({"w": np.ones((16, 4), np.float32)})
        memory.track_scope(scope)
        ring = GhostRing(capacity=2)
        ring.capture(scope, ["w"], step=1)
        c = memory.census()
        self.assertIn("ghost_ring", c["owners"])
        self.assertEqual(c["owners"]["ghost_ring"]["bytes"],
                         ring.nbytes())
        # ghost copies are fresh buffers, never aliases of the scope
        self.assertEqual(c["owners"]["scope"]["count"], 1)
        del ring
        gc.collect()
        c2 = memory.census()
        self.assertNotIn("ghost_ring", c2["owners"])

    def test_pending_fetch_drained_vs_undrained(self):
        payload = jax.device_put(np.ones((64,), np.float32))
        h = _FakeFetchHandle("loss", payload)
        memory.track_fetch_handle(h)
        c = memory.census()
        self.assertEqual(c["owners"]["pending_fetch"]["bytes"],
                         int(payload.nbytes))
        h._value = None  # drained: payload handed off to the caller
        c2 = memory.census()
        self.assertNotIn("pending_fetch", c2["owners"])

    def test_host_bytes_reported_but_not_reconciled(self):
        memory.note_host_bytes("tuning_snapshot", 12345)
        c = memory.census()
        self.assertEqual(c["host_owners"], {"tuning_snapshot": 12345})
        self.assertNotIn("tuning_snapshot", c["owners"])
        memory.note_host_bytes("tuning_snapshot", 0)
        self.assertEqual(memory.census()["host_owners"], {})

    def test_owner_gauge_zeroed_when_owner_vanishes(self):
        scope = _device_scope({"w": np.ones((4, 4), np.float32)})
        memory.track_scope(scope)
        memory.census()
        g = metrics.gauge("pt_hbm_owner_bytes")
        self.assertGreater(g.get(owner="scope"), 0.0)
        memory._SCOPES.clear()
        memory.census()
        self.assertEqual(g.get(owner="scope"), 0.0)


class TestHotGate(unittest.TestCase):
    def _tiny_engine(self):
        import paddle_tpu as fluid
        from paddle_tpu import layers
        from paddle_tpu.core.engine import Engine
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

    def test_disabled_path_does_zero_census_work(self):
        _quiet_gates(self)
        memory.reset()
        self.addCleanup(memory.reset)
        fluid, eng, prog, scope, feed, fetch = self._tiny_engine()
        self.assertFalse(metrics._HOT[0])
        with fluid.scope_guard(scope):
            for _ in range(3):
                eng.run(prog, scope, None, feed, fetch)
        self.assertEqual(memory.stats()["censuses"], 0)
        self.assertIsNone(memory.last_census())

    def test_enable_alone_arms_the_engine_and_counts_censuses(self):
        _quiet_gates(self)
        memory.reset()
        self.addCleanup(memory.reset)
        fluid, eng, prog, scope, feed, fetch = self._tiny_engine()
        memory.enable(True)
        self.assertTrue(metrics._HOT[0])
        self.assertTrue(memory.census_active())
        with fluid.scope_guard(scope):
            for _ in range(3):
                eng.run(prog, scope, None, feed, fetch)
        self.assertEqual(memory.stats()["censuses"], 3)
        # the engine cold path registered the scope: owner visible
        self.assertIn("scope", memory.last_census()["owners"])
        memory.enable(False)
        self.assertFalse(metrics._HOT[0])

    def test_feed_batch_attributed_and_released_on_disable(self):
        _quiet_gates(self)
        memory.reset()
        self.addCleanup(memory.reset)
        fluid, eng, prog, scope, feed, fetch = self._tiny_engine()
        memory.enable(True)
        with fluid.scope_guard(scope):
            eng.run(prog, scope, None, feed, fetch)
        owners = memory.last_census()["owners"]
        self.assertIn("feed", owners)
        self.assertEqual(owners["feed"]["bytes"], feed["x"].nbytes)
        # census off -> the next step must not retain the batch
        memory.enable(False)
        with fluid.scope_guard(scope):
            eng.run(prog, scope, None, feed, fetch)
        self.assertIsNone(eng._census_feed)

    def test_census_cadence_env(self):
        _quiet_gates(self)
        memory.reset()
        self.addCleanup(memory.reset)
        self.addCleanup(os.environ.pop, "PT_HBM_CENSUS_EVERY", None)
        os.environ["PT_HBM_CENSUS_EVERY"] = "3"
        memory.enable(True)
        for _ in range(6):
            memory.step_tick()
        self.assertEqual(memory.stats()["censuses"], 2)


class TestLeakSentinel(unittest.TestCase):
    def test_fires_once_on_monotone_growth(self):
        s = memory.LeakSentinel(window=3, min_bytes=100)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            self.assertEqual(s.feed({"cache": 0}), {})
            self.assertEqual(s.feed({"cache": 60}), {})
            flagged = s.feed({"cache": 150})
            self.assertEqual(flagged, {"cache": 150})
            s.feed({"cache": 200})  # still growing: stays flagged
            hits = [x for x in w if issubclass(x.category,
                                               RuntimeWarning)]
        self.assertEqual(len(hits), 1)  # one-shot per owner
        self.assertIn("cache", str(hits[0].message))
        self.assertEqual(
            metrics.gauge("pt_hbm_leak_suspect_bytes")
            .get(owner="cache"), 140.0)

    def test_silent_on_steady_and_sawtooth(self):
        s = memory.LeakSentinel(window=3, min_bytes=1)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for v in (500, 500, 500):        # steady
                self.assertEqual(s.feed({"scope": v}), {})
            s.reset()
            for v in (100, 900, 200, 800):   # sawtooth (allocator churn)
                self.assertEqual(s.feed({"scope": v}), {})
            self.assertEqual(
                [x for x in w
                 if issubclass(x.category, RuntimeWarning)], [])

    def test_vanished_owner_clears_the_gauge(self):
        s = memory.LeakSentinel(window=2, min_bytes=10)
        s.feed({"ring": 0})
        self.assertEqual(s.feed({"ring": 50}), {"ring": 50})
        # owner gone from the census: recorded as 0, growth negative
        self.assertEqual(s.feed({}), {})
        self.assertEqual(
            metrics.gauge("pt_hbm_leak_suspect_bytes")
            .get(owner="ring"), 0.0)

    def test_below_min_bytes_is_noise(self):
        s = memory.LeakSentinel(window=2, min_bytes=1000)
        s.feed({"x": 0})
        self.assertEqual(s.feed({"x": 999}), {})


class TestDumpsAndWatermark(unittest.TestCase):
    def setUp(self):
        memory.reset()
        self.addCleanup(memory.reset)
        self.dir = _flight_dir(self)

    def test_dump_roundtrip_has_all_sections(self):
        scope = _device_scope({"w": np.ones((8, 8), np.float32)})
        memory.track_scope(scope)
        path = memory.dump("manual", extra={"note": "t"})
        self.assertIsNotNone(path)
        self.assertEqual(memory.find_memdumps(self.dir), [path])
        d = memory.read_memdump(path)
        self.assertEqual(d["header"]["reason"], "manual")
        self.assertEqual(d["header"]["note"], "t")
        self.assertIn("counters", d["header"])
        self.assertEqual(d["census"]["owners"]["scope"]["bytes"], 256)
        self.assertTrue(d["buffers"])
        self.assertEqual(d["buffers"][0]["kind"], "buffer")
        self.assertEqual(set(d), {"header", "census", "buffers",
                                  "donation"})
        self.assertEqual(d["donation"]["kind"], "donation")
        self.assertIn("donated_names", d["donation"])

    def test_watermark_rising_edge_debounce(self):
        self.addCleanup(os.environ.pop, "PT_HBM_LIMIT_BYTES", None)
        self.addCleanup(os.environ.pop,
                        "PT_HBM_DUMP_THRESHOLD_FRAC", None)
        os.environ["PT_HBM_LIMIT_BYTES"] = "1000"
        os.environ["PT_HBM_DUMP_THRESHOLD_FRAC"] = "0.8"
        c = {"live_bytes": 900, "owners": {}, "top_buffers": []}
        self.assertTrue(memory.check_watermark(c))       # rising edge
        self.assertFalse(memory.check_watermark(c))      # debounced
        self.assertFalse(memory.check_watermark(
            {"live_bytes": 500, "owners": {}, "top_buffers": []}))
        # fell below thr/2: re-armed, next crossing dumps again
        self.assertFalse(memory.check_watermark(
            {"live_bytes": 399, "owners": {}, "top_buffers": []}))
        self.assertTrue(memory.check_watermark(c))
        dumps = memory.find_memdumps(self.dir)
        self.assertEqual(len(dumps), 2)
        d = memory.read_memdump(dumps[0])
        self.assertEqual(d["header"]["reason"], "watermark")
        self.assertEqual(d["header"]["limit_bytes"], 1000)
        self.assertAlmostEqual(d["header"]["usage_frac"], 0.9)

    def test_watermark_off_without_threshold(self):
        os.environ.pop("PT_HBM_DUMP_THRESHOLD_FRAC", None)
        self.assertFalse(
            memory.check_watermark({"live_bytes": 10 ** 12}))


class TestOOMPostmortem(unittest.TestCase):
    def setUp(self):
        memory.reset()
        self.addCleanup(memory.reset)
        self.dir = _flight_dir(self)

    def test_is_oom_error_matches_xla_texts(self):
        self.assertTrue(memory.is_oom_error(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes")))
        self.assertTrue(memory.is_oom_error(RuntimeError(
            "Resource exhausted: ran out of HBM")))
        self.assertFalse(memory.is_oom_error(ValueError("bad shape")))

    def test_exactly_one_dump_per_exception(self):
        err = RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 8G")
        p1 = memory.oom_postmortem(err, where="engine_dispatch")
        p2 = memory.oom_postmortem(err, where="fetch")
        self.assertIsNotNone(p1)
        self.assertEqual(p1, p2)
        self.assertEqual(len(memory.find_memdumps(self.dir)), 1)
        self.assertEqual(memory.stats()["oom_postmortems"], 1)
        d = memory.read_memdump(p1)
        self.assertEqual(d["header"]["reason"], "oom")
        self.assertEqual(d["header"]["where"], "engine_dispatch")
        self.assertIn("RESOURCE_EXHAUSTED", d["header"]["error"])
        for section in ("census", "donation"):
            self.assertIsNotNone(d[section])

    def test_wrapped_cause_chain_dedupes(self):
        # async materialization wraps the XLA error (EnforceNotMet
        # carries the message + __cause__); the engine's catch of the
        # WRAPPER must find the tag left on the original
        root = RuntimeError("RESOURCE_EXHAUSTED: OOM in island 2")
        p1 = memory.oom_postmortem(root, where="pending_step_check")
        wrapped = RuntimeError(
            "step failed: RESOURCE_EXHAUSTED: OOM in island 2")
        wrapped.__cause__ = root
        p2 = memory.oom_postmortem(wrapped, where="engine_dispatch")
        self.assertEqual(p1, p2)
        self.assertEqual(len(memory.find_memdumps(self.dir)), 1)

    def test_non_oom_is_a_no_op(self):
        self.assertIsNone(
            memory.oom_postmortem(ValueError("boom"), where="x"))
        self.assertEqual(memory.find_memdumps(self.dir), [])
        self.assertEqual(memory.stats()["oom_postmortems"], 0)

    def test_engine_dispatch_oom_writes_postmortem(self):
        import paddle_tpu as fluid
        from paddle_tpu import layers
        from paddle_tpu.core.engine import Engine
        _quiet_gates(self)
        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            loss = layers.mean(layers.fc(x, size=2))
        scope = Scope()
        with fluid.scope_guard(scope):
            fluid.Executor().run(startup)
        eng = Engine()
        feed = {"x": np.ones((2, 4), np.float32)}
        with fluid.scope_guard(scope):
            eng.run(main, scope, None, feed, [loss.name])

            def boom(*a, **k):
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory while running "
                    "fused computation")

            for traced in eng._cache.values():
                traced.fn = boom
            with self.assertRaises(Exception):
                eng.run(main, scope, None, feed, [loss.name])
        dumps = memory.find_memdumps(self.dir)
        self.assertEqual(len(dumps), 1)
        d = memory.read_memdump(dumps[0])
        self.assertEqual(d["header"]["reason"], "oom")
        self.assertIn("scope", d["census"]["owners"])


class TestTooling(unittest.TestCase):
    def setUp(self):
        memory.reset()
        self.addCleanup(memory.reset)
        self.dir = _flight_dir(self)

    def test_new_families_registered(self):
        names = {f.name for f in metrics.default_registry().collect()}
        for fam in NEW_FAMILIES:
            self.assertIn(fam, names)

    def test_metrics_report_requires_the_new_families(self):
        from tools import metrics_report
        for fam in NEW_FAMILIES:
            self.assertIn(fam, metrics_report.REQUIRED_FAMILIES)

    def test_memdump_to_chrome_trace(self):
        scope = _device_scope({"w": np.ones((8, 8), np.float32)})
        memory.track_scope(scope)
        path = memory.dump("manual")
        evs = export.memdump_to_chrome_trace(path)
        counters = [e for e in evs if e["ph"] == "C"]
        self.assertTrue(counters)
        owner_ctr = next(e for e in counters
                         if e["name"] == "hbm_owner_bytes")
        self.assertEqual(owner_ctr["args"]["scope"], 256)
        bufs = [e for e in evs if e.get("cat") == "memory.buffer"]
        self.assertTrue(bufs)
        self.assertEqual(bufs[0]["args"]["owner"], "scope")
        self.assertTrue(any(e["name"] == "donation" for e in evs))

    def test_timeline_merges_a_memdump_lane(self):
        scope = _device_scope({"w": np.ones((4, 4), np.float32)})
        memory.track_scope(scope)
        path = memory.dump("manual")
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import timeline
        # a directory input auto-expands to the memdump lane
        lanes = timeline._expand("post", self.dir, True)
        self.assertIn(path, [p for _, p in lanes])
        trace = timeline.merge(lanes)
        names = {e.get("name") for e in trace["traceEvents"]}
        self.assertIn("hbm_owner_bytes", names)
        self.assertIn("process_name", names)


if __name__ == "__main__":
    unittest.main()
