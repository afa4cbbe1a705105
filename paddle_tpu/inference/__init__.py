"""Inference engine (reference paddle/fluid/inference/, ~29.4k LoC).

Reference shape: ``CreatePaddlePredictor(AnalysisConfig)`` returns an
``AnalysisPredictor`` that loads the frozen ProgramDesc + persistables,
runs the IR analysis/fusion passes, and serves ``Run``/ZeroCopy calls
(analysis_predictor.h:46, paddle_api.h:338).

TPU-native redesign: the analysis/fusion pass stack is subsumed by XLA —
the frozen program is traced ONCE into a single XLA executable
(core/engine.trace_step), so "analysis" equals compilation. What remains
first-class here:

* ``AnalysisConfig`` — model location + knobs (accelerator on/off; the
  reference's TensorRT/MKLDNN/memory-optim switches are accepted and
  subsumed).
* ``AnalysisPredictor`` — owns a Scope with the loaded persistables,
  compile-caches per input signature, and serves the ZeroCopy contract
  (get_input_tensor / copy_from_cpu / zero_copy_run / copy_to_cpu).
* **AOT**: the compiled computation is serialized with ``jax.export``
  (StableHLO) next to the model (``__aot__/<sig>.pb``); a new process
  deserializes and runs WITHOUT retracing or recompiling the Python
  program — the analog of the reference's pre-analyzed inference
  program + engine snapshot.
"""
from __future__ import annotations

import hashlib
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.engine import trace_step
from ..core.scope import LoDTensor, Scope
from .. import io as _io
from ..executor import Executor
from ..core.place import CPUPlace, TPUPlace, default_place

__all__ = ["AnalysisConfig", "AnalysisPredictor", "PaddleTensor",
           "ZeroCopyTensor", "create_paddle_predictor"]


class AnalysisConfig:
    """Reference paddle_analysis_config.h — the subset that matters on
    TPU, with subsumed knobs accepted as no-ops."""

    def __init__(self, model_dir: str = None, prog_file: str = None,
                 params_file: str = None):
        self._model_dir = model_dir
        self._prog_file = prog_file
        self._params_file = params_file
        self._use_accelerator = True
        self._enable_aot = True
        self._ir_optim = True  # accepted; XLA always optimizes

    # -- model location -----------------------------------------------------
    def set_model(self, model_dir, params_file=None):
        self._model_dir = model_dir
        self._params_file = params_file
        return self

    def model_dir(self):
        return self._model_dir

    # -- device -------------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        """Reference API name; means 'use the accelerator' here (TPU)."""
        self._use_accelerator = True

    def disable_gpu(self):
        self._use_accelerator = False

    def use_gpu(self):
        return self._use_accelerator

    # -- subsumed switches (XLA performs these unconditionally) -------------
    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def enable_memory_optim(self):
        pass

    def switch_use_feed_fetch_ops(self, flag):
        pass

    def switch_specify_input_names(self, flag=True):
        pass

    def set_cpu_math_library_num_threads(self, n):
        pass

    # -- AOT ----------------------------------------------------------------
    def enable_aot(self, flag=True):
        """Serialize/reuse the compiled executable next to the model."""
        self._enable_aot = flag


class PaddleTensor:
    """Simple Run() payload (reference paddle_api.h PaddleTensor)."""

    def __init__(self, data=None, name=""):
        self.name = name
        self.data = np.asarray(data) if data is not None else None
        self.lod = []

    @property
    def shape(self):
        return list(self.data.shape)


class ZeroCopyTensor:
    """Reference ZeroCopyTensor: reads/writes the predictor's own
    buffers, no extra copy through a feed/fetch op."""

    def __init__(self, name: str, predictor: "AnalysisPredictor",
                 is_input: bool):
        self._name = name
        self._pred = predictor
        self._is_input = is_input

    def name(self):
        return self._name

    def copy_from_cpu(self, arr):
        assert self._is_input, "output tensors are read-only"
        self._pred._inputs[self._name] = np.ascontiguousarray(arr)

    def set_lod(self, lod):
        assert self._is_input, "output tensors are read-only"
        self._pred._input_lods[self._name] = [list(lv) for lv in lod]

    def lod(self):
        if self._is_input:
            return self._pred._input_lods.get(self._name, [])
        out = self._pred._outputs[self._name]
        return out.lod() if isinstance(out, LoDTensor) else []

    def copy_to_cpu(self):
        out = self._pred._outputs[self._name]
        return np.asarray(out.array if isinstance(out, LoDTensor)
                          else out)

    def shape(self):
        if self._is_input:
            return list(self._pred._inputs[self._name].shape)
        return list(np.asarray(self.copy_to_cpu()).shape)


class AnalysisPredictor:
    """Load-once, compile-per-signature predictor (reference
    analysis_predictor.h:46)."""

    def __init__(self, config: AnalysisConfig):
        self._config = config
        self._scope = Scope()
        place = default_place() if config.use_gpu() else CPUPlace()
        self._place = place
        exe = Executor(place)
        with _scope_guard(self._scope):
            (self._program, self._feed_names,
             fetch_vars) = _io.load_inference_model(
                config.model_dir(), exe,
                model_filename=config._prog_file,
                params_filename=config._params_file)
        self._fetch_names = [v.name for v in fetch_vars]
        self._inputs: Dict[str, np.ndarray] = {}
        self._input_lods: Dict[str, list] = {}
        self._outputs: Dict[str, object] = {}
        self._compiled = {}          # sig -> callable
        self._param_store = {}       # sig -> (d_params, c_params)
        self._aot_dir = os.path.join(config.model_dir(), "__aot__")
        _obs_memory().track_predictor(self)

    # -- ZeroCopy contract --------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def get_input_tensor(self, name) -> ZeroCopyTensor:
        assert name in self._feed_names, name
        return ZeroCopyTensor(name, self, is_input=True)

    def get_output_tensor(self, name) -> ZeroCopyTensor:
        assert name in self._fetch_names, name
        return ZeroCopyTensor(name, self, is_input=False)

    def zero_copy_run(self):
        feeds = dict(self._inputs)
        outs = self._run_feeds(feeds, dict(self._input_lods))
        self._outputs = dict(zip(self._fetch_names, outs))

    # -- classic Run --------------------------------------------------------
    def run(self, inputs: Sequence[PaddleTensor]) -> List[PaddleTensor]:
        feeds, lods = {}, {}
        for i, t in enumerate(inputs):
            name = t.name or self._feed_names[i]
            feeds[name] = np.asarray(t.data)
            if t.lod:
                lods[name] = [list(lv) for lv in t.lod]
        outs = self._run_feeds(feeds, lods)
        result = []
        for name, o in zip(self._fetch_names, outs):
            arr = np.asarray(o.array if isinstance(o, LoDTensor) else o)
            pt = PaddleTensor(arr, name)
            if isinstance(o, LoDTensor):
                pt.lod = o.lod()
            result.append(pt)
        return result

    def clone(self) -> "AnalysisPredictor":
        """A predictor sharing this one's loaded (read-only) weights and
        AOT artifacts, with its own feed/fetch buffers and compile
        cache — the reference contract (analysis_predictor.h Clone):
        cheap per-thread handles over one set of persistables, NOT a
        second load of the model from disk."""
        twin = AnalysisPredictor.__new__(AnalysisPredictor)
        twin._config = self._config
        twin._scope = self._scope          # read-only persistables
        twin._place = self._place
        twin._program = self._program
        twin._feed_names = list(self._feed_names)
        twin._fetch_names = list(self._fetch_names)
        twin._inputs = {}
        twin._input_lods = {}
        twin._outputs = {}
        twin._compiled = {}
        twin._param_store = {}
        twin._aot_dir = self._aot_dir
        _obs_memory().track_predictor(twin)
        return twin

    # -- compile / AOT ------------------------------------------------------
    def _sig_of(self, feeds, lods):
        return tuple((n, tuple(feeds[n].shape), str(feeds[n].dtype),
                      tuple(map(tuple, lods.get(n, []))))
                     for n in sorted(feeds))

    def _aot_path(self, sig):
        # keyed on program CONTENT + feed signature: a re-saved model
        # with identical shapes must not serve a stale executable
        prog_h = hashlib.sha256(
            self._program.serialize_to_string()).hexdigest()[:16]
        h = hashlib.sha256(
            (prog_h + repr(sig)).encode()).hexdigest()[:16]
        return os.path.join(self._aot_dir, f"{h}.stablehlo")

    def _param_arrays(self, names):
        out = {}
        for n in names:
            v = self._scope.find_var(n)
            val = v.get_value()
            out[n] = jnp.asarray(np.asarray(
                val.array if isinstance(val, LoDTensor) else val))
        return out

    def _run_feeds(self, feeds, lods=None):
        lods = lods or {}
        sig = self._sig_of(feeds, lods)
        entry = self._compiled.get(sig)
        if entry is None:
            entry = self._build(sig, feeds, lods)
            self._compiled[sig] = entry
        return entry(feeds)

    def _build(self, sig, feeds, lods):
        feed_sig = {n: jax.ShapeDtypeStruct(a.shape,
                                            jnp.result_type(a.dtype))
                    for n, a in feeds.items()}
        key = jnp.zeros((2,), jnp.uint32)       # inference: no rng use

        aot_path = self._aot_path(sig)
        fn = None
        fetch_lods = {}
        if self._config._enable_aot and os.path.exists(aot_path) \
                and not lods:
            try:
                fn, donated, const = self._load_aot(aot_path)
            except Exception as exc:
                import warnings
                warnings.warn(
                    f"ignoring AOT artifact {aot_path!r} "
                    f"({type(exc).__name__}: {exc}); re-tracing",
                    stacklevel=2)
                fn = None       # corrupt/stale AOT: fall back to trace
        if fn is None:
            traced = trace_step(self._program, 0, feed_sig, lods,
                                self._fetch_names, self._scope)
            donated, const = traced.donated_names, traced.const_names
            fn = traced.fn
            fetch_lods = traced.fetch_lods
            if self._config._enable_aot and not lods:
                self._save_aot(aot_path, fn, donated, const, feed_sig,
                               key)

        d_params = self._param_arrays(donated)
        c_params = self._param_arrays(const)
        # held per-signature on the predictor so the HBM observatory can
        # attribute these device buffers to owner "predictor" instead of
        # reporting them as orphans (observability/memory.py census)
        self._param_store[sig] = (d_params, c_params)

        def call(feed_arrays):
            # device arrays pass through untouched (the serving engine
            # feeds jnp buffers); host arrays take the canonical
            # np->jnp copy
            arrs = {n: a if isinstance(a, jax.Array)
                    else jnp.asarray(np.asarray(a))
                    for n, a in feed_arrays.items()}
            fetches, updated, *_ = fn(dict(d_params), c_params, arrs,
                                      key)
            # donated buffers are consumed by the executable; carry the
            # updated state forward so the next call has live arrays
            d_params.update(updated)
            outs = []
            for name, v in zip(self._fetch_names, fetches):
                lod = fetch_lods.get(name)
                outs.append(LoDTensor(v, lod) if lod else v)
            return outs

        return call

    def _save_aot(self, path, fn, donated, const, feed_sig, key):
        try:
            from jax import export as jax_export

            def _sig_of_var(n):
                arr = np.asarray(_scope_val(self._scope, n))
                return jax.ShapeDtypeStruct(arr.shape,
                                            jnp.result_type(arr.dtype))

            d_sig = {n: _sig_of_var(n) for n in donated}
            c_sig = {n: _sig_of_var(n) for n in const}
            exp = jax_export.export(fn)(
                d_sig, c_sig, feed_sig,
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            os.makedirs(self._aot_dir, exist_ok=True)
            with open(path, "wb") as f:
                f.write(exp.serialize())
            # JSON, not pickle: the sidecar rides along with model dirs
            # from arbitrary sources, and unpickling untrusted bytes
            # executes code
            meta = {"donated": list(donated), "const": list(const)}
            import json
            with open(path + ".meta", "w") as f:
                json.dump(meta, f)
        except Exception as exc:
            # AOT is an optimization; never fail inference over it —
            # but a silently-broken export path is undiagnosable, so
            # say what went wrong (once per process per artifact dir)
            if self._aot_dir not in _AOT_SAVE_WARNED:
                _AOT_SAVE_WARNED.add(self._aot_dir)
                import warnings
                warnings.warn(
                    f"AOT export to {path!r} failed "
                    f"({type(exc).__name__}: {exc}); inference "
                    "continues via the freshly-traced executable but "
                    "new processes will retrace", stacklevel=2)

    def _load_aot(self, path):
        from jax import export as jax_export
        import json
        with open(path, "rb") as f:
            exp = jax_export.deserialize(f.read())
        with open(path + ".meta") as f:
            meta = json.load(f)

        def fn(donated, const, feeds, key):
            return exp.call(donated, const, feeds, key)

        return fn, meta["donated"], meta["const"]


# dirs whose AOT-save failure has already been reported (warn once)
_AOT_SAVE_WARNED = set()


def _obs_memory():
    from ..observability import memory as _mem
    return _mem


def _scope_val(scope, name):
    val = scope.find_var(name).get_value()
    return val.array if isinstance(val, LoDTensor) else val


def _scope_guard(scope):
    from ..executor import scope_guard
    return scope_guard(scope)


def create_paddle_predictor(config: AnalysisConfig) -> AnalysisPredictor:
    """Reference CreatePaddlePredictor<AnalysisConfig>
    (paddle_api.h:338)."""
    return AnalysisPredictor(config)
