"""Serving export path: freeze a Program into bucketed prefill/decode
executables (docs/SERVING.md).

The export contract
-------------------
A serving-capable model is TWO frozen Programs sharing one set of
parameter values (identical ``ParamAttr`` names, one startup run, two
``save_inference_model`` dirs):

* **prefill** — feeds ``tokens [B,S]`` int64, ``pos [B,S]`` int64,
  additive float ``mask [B,S,S]``; fetches ``logits [B,S,V]`` plus
  per-layer ``k_i``/``v_i`` ``[B,S,H]`` (the prompt's KV rows, written
  into cache pages by the engine);
* **decode** — feeds ``token [B,1]``, ``pos [B,1]``, per-layer dense
  ``cache_k_i``/``cache_v_i`` ``[B,S,H]`` (gathered from pages),
  ``mask [B,1,S+1]``; fetches ``logits [B,1,V]`` plus the new token's
  ``k_i``/``v_i`` ``[B,1,H]``.

Masks and position ids are computed HOST-side and fed — the frozen
graph needs no iota/comparison ops, and deadline/length policy changes
never retrace. Every dispatch uses a FIXED batch ``B`` and a sequence
length drawn from the declared buckets (``BucketSpec``), so the
predictor's per-signature compile cache plus a ``warmup()`` sweep
guarantee continuous-batching joins never retrace; the AOT StableHLO
artifacts the predictor writes under ``<dir>/__aot__/`` make a fresh
server process skip even the first trace.

Bit-identity (the parity contract tests/test_serving.py pins): every
op in the exported graphs is row-independent (per-row matmul /
softmax / embedding / elementwise), padded rows and masked positions
contribute exactly-zero attention weight (additive ``-1e30`` absorbs
any finite stale score, then underflows to 0.0 in softmax), so a
request's tokens are bitwise identical whether it runs alone or joins
a continuous batch.

Sharding: when the model exceeds one chip, ``resolve_serving_mesh``
(``PT_SERVE_MESH`` = e.g. ``"fsdp=2,tp=4"``) builds the PR 15
``MeshSpec``/``SpecLayout`` strategy and the frozen step is traced
SPMD through the same ``trace_step`` mesh path training uses; on a
single device the spec is ignored with a warning so CPU CI exercises
the gate.
"""
from __future__ import annotations

import json
import math
import os
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["BucketSpec", "bucket_for", "build_book_lm",
           "export_serving_model", "load_serving_model",
           "FrozenServingModel", "resolve_serving_mesh",
           "reference_generate", "NEG_MASK"]

# additive mask value for forbidden attention positions: large enough
# that any finite stale score is absorbed exactly (|score| is far
# below ulp(1e30) ~ 1e21, so score + -1e30 == -1e30 bitwise) and
# exp(-1e30 - max) underflows to exactly 0.0 — the two facts the
# bit-identity parity contract rests on
NEG_MASK = -1e30

MANIFEST = "serving.json"


class BucketSpec:
    """Declared dispatch signatures: one fixed batch size plus sorted
    prefill-length and decode-cache-length buckets. Every executable
    the engine ever dispatches has shape (batch, one of these
    lengths); ``FrozenServingModel.warmup`` compiles them all."""

    def __init__(self, batch: int = 4,
                 prefill_lens: Sequence[int] = (16,),
                 cache_lens: Sequence[int] = (48,)):
        self.batch = int(batch)
        self.prefill_lens = tuple(sorted(int(x) for x in prefill_lens))
        self.cache_lens = tuple(sorted(int(x) for x in cache_lens))
        if not self.prefill_lens or not self.cache_lens:
            raise ValueError("need at least one bucket per phase")

    @property
    def max_context(self) -> int:
        """Longest supported sequence: the decode cache holds at most
        max(cache_lens) tokens before the step that appends the next."""
        return self.cache_lens[-1]

    def to_dict(self) -> dict:
        return {"batch": self.batch,
                "prefill_lens": list(self.prefill_lens),
                "cache_lens": list(self.cache_lens)}

    @classmethod
    def from_dict(cls, d) -> "BucketSpec":
        return cls(d["batch"], d["prefill_lens"], d["cache_lens"])


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n; raises when the request outgrows the
    declared signatures (admission rejects it instead of retracing)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"length {n} exceeds declared buckets {buckets}")


# ---------------------------------------------------------------------------
# book model: a small single-head decoder LM built from existing layers
# ---------------------------------------------------------------------------

def _attn_layer(layers, ParamAttr, h, mask, i, hidden,
                cache_k=None, cache_v=None):
    """One pre-residual attention + FFN block; returns (h, k, v) where
    k/v are THIS segment's rows (the prompt's in prefill, the new
    token's in decode)."""
    def pa(n):
        return ParamAttr(name=f"lm.l{i}.{n}.w")

    def ba(n):
        return ParamAttr(name=f"lm.l{i}.{n}.b")

    q = layers.fc(h, hidden, num_flatten_dims=2,
                  param_attr=pa("q"), bias_attr=ba("q"))
    k = layers.fc(h, hidden, num_flatten_dims=2,
                  param_attr=pa("k"), bias_attr=ba("k"))
    v = layers.fc(h, hidden, num_flatten_dims=2,
                  param_attr=pa("v"), bias_attr=ba("v"))
    if cache_k is not None:
        full_k = layers.concat([cache_k, k], axis=1)
        full_v = layers.concat([cache_v, v], axis=1)
    else:
        full_k, full_v = k, v
    scores = layers.matmul(q, full_k, transpose_y=True,
                           alpha=1.0 / math.sqrt(hidden))
    scores = layers.elementwise_add(scores, mask)
    probs = layers.softmax(scores, axis=-1)
    att = layers.matmul(probs, full_v)
    o = layers.fc(att, hidden, num_flatten_dims=2,
                  param_attr=pa("o"), bias_attr=ba("o"))
    h = layers.elementwise_add(h, o)
    f = layers.fc(h, hidden * 2, num_flatten_dims=2, act="relu",
                  param_attr=pa("f1"), bias_attr=ba("f1"))
    f = layers.fc(f, hidden, num_flatten_dims=2,
                  param_attr=pa("f2"), bias_attr=ba("f2"))
    h = layers.elementwise_add(h, f)
    return h, k, v


def build_book_lm(vocab: int = 50, hidden: int = 16,
                  num_layers: int = 2, max_len: int = 128):
    """Build the serving book model: (prefill_prog, decode_prog,
    startup_prog, meta). Both programs reference the SAME parameter
    names, so one startup run initializes weights both can serve."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.param_attr import ParamAttr

    meta = {"vocab": vocab, "hidden": hidden,
            "num_layers": num_layers, "max_len": max_len}

    def embed(toks, pos):
        emb = layers.embedding(
            toks, size=[vocab, hidden],
            param_attr=ParamAttr(name="lm.tok_emb"))
        pemb = layers.embedding(
            pos, size=[max_len, hidden],
            param_attr=ParamAttr(name="lm.pos_emb"))
        return layers.elementwise_add(emb, pemb)

    def head(h):
        return layers.fc(h, vocab, num_flatten_dims=2,
                         param_attr=ParamAttr(name="lm.head.w"),
                         bias_attr=ParamAttr(name="lm.head.b"))

    prefill, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prefill, startup):
        toks = layers.data("tokens", [-1], dtype="int64")
        pos = layers.data("pos", [-1], dtype="int64")
        mask = layers.data("mask", [-1, -1], dtype="float32")
        h = embed(toks, pos)
        kvs = []
        for i in range(num_layers):
            h, k, v = _attn_layer(layers, ParamAttr, h, mask, i, hidden)
            kvs.extend([k, v])
        logits = head(h)
    meta["prefill_fetches"] = [logits.name] + [t.name for t in kvs]

    decode, dec_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(decode, dec_startup):
        # shape [1] (not [-1]): lookup_table squeezes trailing-1 id
        # dims, and shape inference must see the same squeeze the
        # runtime [B,1] feed takes
        toks = layers.data("token", [1], dtype="int64")
        pos = layers.data("pos", [1], dtype="int64")
        mask = layers.data("mask", [-1, -1], dtype="float32")
        caches = []
        for i in range(num_layers):
            caches.append(
                (layers.data(f"cache_k_{i}", [-1, hidden],
                             dtype="float32"),
                 layers.data(f"cache_v_{i}", [-1, hidden],
                             dtype="float32")))
        # lookup_table squeezes trailing-1 id dims ([B,1] ids embed to
        # [B,H]); restore the length-1 sequence axis the attention
        # stack expects
        h = layers.unsqueeze(embed(toks, pos), [1])
        kvs = []
        for i, (ck, cv) in enumerate(caches):
            h, k, v = _attn_layer(layers, ParamAttr, h, mask, i,
                                  hidden, cache_k=ck, cache_v=cv)
            kvs.extend([k, v])
        logits = head(h)
    meta["decode_fetches"] = [logits.name] + [t.name for t in kvs]
    # decode's params carry the same names; its startup is never run
    return prefill, decode, startup, meta


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------

def export_serving_model(dirname: str, exe, prefill_prog, decode_prog,
                         meta: dict,
                         buckets: Optional[BucketSpec] = None) -> dict:
    """Freeze an initialized model (scope already holds the weights)
    into ``<dirname>/prefill`` + ``<dirname>/decode`` inference dirs
    plus a ``serving.json`` manifest. Returns the manifest dict."""
    import paddle_tpu as fluid
    num_layers = int(meta["num_layers"])
    pre_feeds = ["tokens", "pos", "mask"]
    dec_feeds = ["token", "pos", "mask"] + \
        [f"cache_{kv}_{i}" for i in range(num_layers)
         for kv in ("k", "v")]
    fluid.io.save_inference_model(
        os.path.join(dirname, "prefill"), pre_feeds,
        list(meta["prefill_fetches"]), exe, main_program=prefill_prog)
    fluid.io.save_inference_model(
        os.path.join(dirname, "decode"), dec_feeds,
        list(meta["decode_fetches"]), exe, main_program=decode_prog)
    manifest = dict(meta)
    manifest["prefill_feeds"] = pre_feeds
    manifest["decode_feeds"] = dec_feeds
    manifest["buckets"] = (buckets or BucketSpec()).to_dict()
    with open(os.path.join(dirname, MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)
    return manifest


def resolve_serving_mesh(spec: Optional[str] = None):
    """Parse a ``"data=2,tp=4"``-style spec (argument, else the
    ``PT_SERVE_MESH`` env) into a PR 15 ``MeshSpec``. Returns None —
    with a warning when a spec was asked for — unless more than one
    device is attached: single-chip serving always takes the unsharded
    path, which is what CPU CI exercises."""
    if spec is None:
        spec = os.environ.get("PT_SERVE_MESH", "")
    spec = (spec or "").strip()
    if not spec:
        return None
    axes = {}
    for item in spec.split(","):
        k, _, v = item.strip().partition("=")
        if k not in ("data", "fsdp", "tp"):
            raise ValueError(
                f"unknown serving mesh axis {k!r} in {spec!r}; "
                f"known: data, fsdp, tp")
        axes[k] = int(v)
    if jax.device_count() < 2:
        warnings.warn(
            f"PT_SERVE_MESH={spec!r} requested but only "
            f"{jax.device_count()} device is attached; serving "
            "unsharded", stacklevel=2)
        return None
    from ...parallel.mesh import MeshSpec
    return MeshSpec(**axes)


class FrozenServingModel:
    """The loaded serving artifact: two AnalysisPredictors (sharing the
    export's AOT cache) plus the manifest. Raw-array interface — the
    scheduler feeds numpy/jax arrays and reads jax fetches without the
    PaddleTensor wrapping."""

    def __init__(self, dirname: str, buckets: Optional[BucketSpec]
                 = None, mesh_spec: Optional[str] = None):
        from .. import AnalysisConfig, create_paddle_predictor
        with open(os.path.join(dirname, MANIFEST)) as f:
            self.meta = json.load(f)
        self.buckets = buckets or BucketSpec.from_dict(
            self.meta["buckets"])
        self.num_layers = int(self.meta["num_layers"])
        self.hidden = int(self.meta["hidden"])
        self.vocab = int(self.meta["vocab"])
        self.mesh_spec = resolve_serving_mesh(mesh_spec)
        self._strategy = self._build_strategy()

        def _cfg(sub):
            cfg = AnalysisConfig(os.path.join(dirname, sub))
            if jax.default_backend() == "cpu":
                cfg.disable_gpu()
            return cfg

        self._pp = create_paddle_predictor(_cfg("prefill"))
        self._dp = create_paddle_predictor(_cfg("decode"))
        if self.mesh_spec is not None:
            self._shard_predictors()

    # -- sharding (multi-chip models, PR 15 mesh) ----------------------------

    def _build_strategy(self):
        if self.mesh_spec is None:
            return None
        from ...parallel.strategy import DistributedStrategy, SpecLayout
        layout = SpecLayout(fsdp=self.mesh_spec.fsdp != 1,
                            tp=self.mesh_spec.tp != 1)
        return DistributedStrategy.from_mesh_spec(
            self.mesh_spec, layout, devices=jax.devices())

    def _shard_predictors(self):
        """Reroute both predictors' compiles through trace_step's mesh
        path: feeds shard on batch, params place per the SpecLayout
        rules — the same SPMD pipeline training uses, so a model too
        big for one chip serves from the whole mesh."""
        from ...core.engine import trace_step as _ts
        strategy = self._strategy
        mesh = strategy.mesh

        for pred in (self._pp, self._dp):
            def _build(sig, feeds, lods, _p=pred):
                feed_sig = {n: jax.ShapeDtypeStruct(
                    a.shape, jnp.result_type(a.dtype))
                    for n, a in feeds.items()}
                traced = _ts(_p._program, 0, feed_sig, lods,
                             _p._fetch_names, _p._scope, mesh=mesh,
                             strategy=strategy)
                d_params = _p._param_arrays(traced.donated_names)
                c_params = _p._param_arrays(traced.const_names)
                _p._param_store[sig] = (d_params, c_params)
                key = jnp.zeros((2,), jnp.uint32)

                def call(feed_arrays):
                    arrs = {n: a if isinstance(a, jax.Array)
                            else jnp.asarray(np.asarray(a))
                            for n, a in feed_arrays.items()}
                    fetches, updated, *_ = traced.fn(
                        dict(d_params), c_params, arrs, key)
                    d_params.update(updated)
                    return list(fetches)

                return call
            pred._build = _build

    # -- raw-array entry points ---------------------------------------------

    def prefill(self, tokens, pos, mask):
        """``tokens``/``pos`` int64 ``[B,S]``, ``mask`` f32 ``[B,S,S]``
        -> (logits ``[B,S,V]`` np, k ``[L,B,S,H]`` jnp, v same)."""
        outs = self._pp._run_feeds(
            {"tokens": np.asarray(tokens, np.int64),
             "pos": np.asarray(pos, np.int64),
             "mask": np.asarray(mask, np.float32)})
        logits = np.asarray(outs[0])
        L = self.num_layers
        k = jnp.stack([outs[1 + 2 * i] for i in range(L)])
        v = jnp.stack([outs[2 + 2 * i] for i in range(L)])
        return logits, k, v

    def decode(self, token, pos, mask, cache_k, cache_v):
        """``token``/``pos`` int64 ``[B,1]``, ``mask`` f32
        ``[B,1,S+1]``, ``cache_k``/``cache_v`` ``[L,B,S,H]`` (jax) ->
        (logits ``[B,V]`` np, k_new ``[L,B,H]`` jnp, v_new same)."""
        feeds = {"token": np.asarray(token, np.int64),
                 "pos": np.asarray(pos, np.int64),
                 "mask": np.asarray(mask, np.float32)}
        for i in range(self.num_layers):
            feeds[f"cache_k_{i}"] = cache_k[i]
            feeds[f"cache_v_{i}"] = cache_v[i]
        outs = self._dp._run_feeds(feeds)
        logits = np.asarray(outs[0])[:, 0, :]
        L = self.num_layers
        k_new = jnp.stack([outs[1 + 2 * i][:, 0, :] for i in range(L)])
        v_new = jnp.stack([outs[2 + 2 * i][:, 0, :] for i in range(L)])
        return logits, k_new, v_new

    # -- compile-ahead ------------------------------------------------------

    def warmup(self) -> int:
        """Trace (or AOT-load) every declared (batch, bucket)
        signature so steady-state dispatch NEVER retraces — the
        shape-bucketed join contract. Returns the number of
        signatures compiled."""
        B = self.buckets.batch
        n = 0
        for S in self.buckets.prefill_lens:
            self.prefill(np.zeros((B, S), np.int64),
                         np.zeros((B, S), np.int64),
                         np.full((B, S, S), NEG_MASK, np.float32))
            n += 1
        for S in self.buckets.cache_lens:
            zero = jnp.zeros(
                (self.num_layers, B, S, self.hidden), jnp.float32)
            self.decode(np.zeros((B, 1), np.int64),
                        np.zeros((B, 1), np.int64),
                        np.full((B, 1, S + 1), NEG_MASK, np.float32),
                        zero, zero)
            n += 1
        return n


def load_serving_model(dirname: str,
                       buckets: Optional[BucketSpec] = None,
                       mesh_spec: Optional[str] = None
                       ) -> FrozenServingModel:
    return FrozenServingModel(dirname, buckets=buckets,
                              mesh_spec=mesh_spec)


# ---------------------------------------------------------------------------
# host-side mask/feed builders (shared by engine + solo baseline)
# ---------------------------------------------------------------------------

def prefill_feeds(prompts: List[List[int]], S: int, B: int):
    """Padded prefill feeds for up to B prompts: causal mask rows for
    real tokens, NEG_MASK everywhere else (dead rows soften to a
    uniform softmax — finite, unused)."""
    tokens = np.zeros((B, S), np.int64)
    pos = np.zeros((B, S), np.int64)
    mask = np.full((B, S, S), NEG_MASK, np.float32)
    for b, p in enumerate(prompts[:B]):
        n = len(p)
        tokens[b, :n] = p
        pos[b, :n] = np.arange(n)
        tri = np.triu(np.ones((n, n), bool), k=1)
        mask[b, :n, :n] = np.where(tri, NEG_MASK, 0.0)
    return tokens, pos, mask


def decode_feeds(last_tokens: List[Optional[int]],
                 lens: List[int], S: int, B: int):
    """Decode feeds for one step: row b attends its ``lens[b]`` cache
    positions plus itself (slot S); everything else NEG_MASK."""
    token = np.zeros((B, 1), np.int64)
    pos = np.zeros((B, 1), np.int64)
    mask = np.full((B, 1, S + 1), NEG_MASK, np.float32)
    for b, t in enumerate(last_tokens[:B]):
        if t is None:
            continue
        token[b, 0] = t
        pos[b, 0] = lens[b]
        mask[b, 0, :lens[b]] = 0.0
        mask[b, 0, S] = 0.0          # the new token attends itself
    return token, pos, mask


def reference_generate(model: FrozenServingModel, prompt: List[int],
                       max_new_tokens: int) -> List[int]:
    """The parity baseline: run ONE request alone through the
    predictors with a dense host-side cache — same buckets, same
    executables, row 0 of a padded batch. tests/test_serving.py
    asserts the continuous-batching engine's tokens are bit-identical
    to this."""
    bk = model.buckets
    B = bk.batch
    Sp = bucket_for(len(prompt), bk.prefill_lens)
    tokens, pos, mask = prefill_feeds([list(prompt)], Sp, B)
    logits, k, v = model.prefill(tokens, pos, mask)
    n = len(prompt)
    out = [int(np.argmax(logits[0, n - 1]))]
    # dense cache, row 0 live: [L, B, cap, H] grown bucket by bucket
    k = np.asarray(k)[:, :, :n, :]
    v = np.asarray(v)[:, :, :n, :]
    while len(out) < max_new_tokens:
        S = bucket_for(n, bk.cache_lens)
        L, _, _, H = k.shape
        ck = np.zeros((L, B, S, H), np.float32)
        cv = np.zeros((L, B, S, H), np.float32)
        ck[:, :, :n, :] = k
        cv[:, :, :n, :] = v
        token, dpos, dmask = decode_feeds(
            [out[-1]] + [None] * (B - 1), [n] * B, S, B)
        logits, k_new, v_new = model.decode(
            token, dpos, dmask, jnp.asarray(ck), jnp.asarray(cv))
        out.append(int(np.argmax(logits[0])))
        k = np.concatenate(
            [k, np.asarray(k_new)[:, :, None, :]], axis=2)
        v = np.concatenate(
            [v, np.asarray(v_new)[:, :, None, :]], axis=2)
        n += 1
    return out
