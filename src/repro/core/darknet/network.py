"""Darknet network builder: cfg sections -> params + compiled forward.

Mirrors the paper's flow (Fig. 1): parse the Darknet description, map every
conv/deconv/FC layer onto the compute engine, keep the rest as cheap
elementwise/pooling glue.  Inference only (the paper's framework is an
inference accelerator); weights come from init or a checkpoint.

Deployment shape follows the toolflow pattern (fpgaConvNet, CNN2Gate):
plan once at build, then `Network.compile(params, batch_size)` lowers the
whole planned layer list into ONE compiled artifact (`CompiledNetwork`) —
a single jit trace, engine op plan captured as static dispatch counts, and
every subsequent call a straight executable invocation.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
import warnings
from typing import Any, Iterable

import jax
import jax.numpy as jnp

from repro.core import ComputeEngine, backends
from repro.core.darknet import cfg as cfg_mod
from repro.core.darknet import layers as L


@dataclasses.dataclass
class LayerPlan:
    index: int
    type: str
    options: dict[str, Any]
    out_shape: tuple  # (H, W, C) or (N,)


class Network:
    """Built from a darknet cfg; functional apply(params, x).

    The output is the last layer's, or, for a detector, the tuple of its
    ``[yolo]`` layers' outputs in cfg order, as darknet's detectors return
    theirs.  `layer_counts` counts the planned layers by kind."""

    def __init__(self, cfg_text: str, engine: ComputeEngine | None = None):
        self.engine = engine or ComputeEngine()
        self.sections = cfg_mod.parse_cfg(cfg_text)
        net = self.sections[0]
        self.in_shape = (net.get("height"), net.get("width"),
                         net.get("channels"))
        self.plans: list[LayerPlan] = []
        self._plan()

    # ------------------------------------------------------------- planning
    def _plan(self):
        h, w, c = self.in_shape
        shapes: list[tuple] = []
        for i, s in enumerate(self.sections[1:]):
            t = s.type
            if t == "convolutional":
                size, stride = s.get("size", 3), s.get("stride", 1)
                pad = cfg_mod.conv_pad(s, size)
                f = s.get("filters", 1)
                h = (h + 2 * pad - size) // stride + 1
                w = (w + 2 * pad - size) // stride + 1
                c = f
            elif t == "deconvolutional":
                size, stride = s.get("size", 3), s.get("stride", 1)
                pad = cfg_mod.conv_pad(s, size)
                f = s.get("filters", 1)
                h = (h - 1) * stride + size - 2 * pad
                w = (w - 1) * stride + size - 2 * pad
                c = f
            elif t == "maxpool":
                size, stride = s.get("size", 2), s.get("stride", 2)
                pad = cfg_mod.maxpool_pad(s, size)
                h = (h + pad - size) // stride + 1
                w = (w + pad - size) // stride + 1
            elif t == "avgpool":
                h, w = 1, 1
            elif t == "upsample":
                stride = s.get("stride", 2)
                h, w = h * stride, w * stride
            elif t == "route":
                idxs = [j if j >= 0 else len(shapes) + j
                        for j in s.get("layers")]
                h, w, _ = shapes[idxs[0]]
                c = sum(shapes[j][2] for j in idxs)
            elif t == "shortcut":
                pass  # same shape
            elif t == "connected":
                n = s.get("output")
                h, w, c = 1, 1, n
            elif t in ("softmax", "dropout"):
                pass
            elif t == "yolo":
                n = len(s.get("mask", range(s.get("num", 1))))
                want = n * (5 + s.get("classes", 20))
                if c != want:
                    raise ValueError(f"layer {i}: [yolo] with {n} anchors "
                                     f"needs {want} channels, got {c}")
            else:
                raise ValueError(f"unplanned layer {t}")
            shapes.append((h, w, c))
            self.plans.append(LayerPlan(i, t, dict(s.options), (h, w, c)))
        self.out_shape = shapes[-1]
        self.layer_counts = dict(collections.Counter(
            p.type for p in self.plans))

    # ----------------------------------------------------------------- init
    def init(self, key) -> dict:
        params: dict[str, Any] = {}
        h, w, c = self.in_shape
        shapes = []
        cur_c = c
        cur_hw = (h, w)
        for p in self.plans:
            t, o = p.type, p.options
            if t == "convolutional":
                key, sub = jax.random.split(key)
                params[f"l{p.index}"] = L.init_conv(
                    sub, o.get("size", 3), cur_c, o.get("filters", 1),
                    o.get("batch_normalize", 0))
            elif t == "deconvolutional":
                key, sub = jax.random.split(key)
                params[f"l{p.index}"] = L.init_deconv(
                    sub, o.get("size", 3), cur_c, o.get("filters", 1),
                    o.get("batch_normalize", 0))
            elif t == "connected":
                key, sub = jax.random.split(key)
                nin = cur_hw[0] * cur_hw[1] * cur_c
                params[f"l{p.index}"] = L.init_connected(sub, nin,
                                                         o.get("output"))
            cur_hw, cur_c = p.out_shape[:2], p.out_shape[2]
            shapes.append(p.out_shape)
        return params

    # -------------------------------------------------------------- forward
    def apply(self, params: dict, x):
        """x: (B, H, W, C) -> network output (a tuple of the heads' for a
        network with ``[yolo]`` layers)."""
        eng = self.engine
        outputs: list = []
        heads: list = []
        for p in self.plans:
            t, o = p.type, p.options
            if t == "convolutional":
                size = o.get("size", 3)
                pad = cfg_mod.conv_pad(o, size)
                x = L.conv2d(eng, params[f"l{p.index}"], x, size=size,
                             stride=o.get("stride", 1), pad=pad,
                             act=o.get("activation", "leaky"),
                             batch_normalize=bool(o.get("batch_normalize", 0)))
            elif t == "deconvolutional":
                size = o.get("size", 3)
                pad = cfg_mod.conv_pad(o, size)
                x = L.deconv2d(eng, params[f"l{p.index}"], x, size=size,
                               stride=o.get("stride", 1), pad=pad,
                               act=o.get("activation", "leaky"),
                               batch_normalize=bool(o.get("batch_normalize", 0)))
            elif t == "maxpool":
                size = o.get("size", 2)
                x = L.maxpool(x, size=size, stride=o.get("stride", 2),
                              pad=cfg_mod.maxpool_pad(o, size))
            elif t == "avgpool":
                x = L.avgpool_global(x)
            elif t == "upsample":
                x = L.upsample(x, stride=o.get("stride", 2))
            elif t == "route":
                idxs = [j if j >= 0 else p.index + j for j in o["layers"]]
                x = L.route([outputs[j] for j in idxs])
            elif t == "shortcut":
                j = o["from"]
                j = j if j >= 0 else p.index + j
                x = L.shortcut(x, outputs[j], act=o.get("activation", "linear"))
            elif t == "connected":
                x = L.connected(eng, params[f"l{p.index}"], x,
                                act=o.get("activation", "linear"))
            elif t == "softmax":
                x = L.softmax(x)
            elif t == "dropout":
                pass  # inference no-op
            elif t == "yolo":
                x = L.yolo(x, classes=o.get("classes", 20),
                           scale_x_y=o.get("scale_x_y", 1.0))
                heads.append(x)
            outputs.append(x)
        return tuple(heads) if heads else x

    def num_params(self, params) -> int:
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))

    # -------------------------------------------------------------- compile
    def compile(self, params: dict, batch_size: int = 1, *,
                dtype=jnp.float32, donate_params: bool = False,
                lint: str | None = None) -> "CompiledNetwork":
        """Lower the planned layer list into a single compiled artifact.

        One jit trace happens here (AOT lower + compile); every
        `CompiledNetwork.__call__` afterwards is a straight executable
        invocation — no retracing, no per-layer Python dispatch.

        Args:
          params: the param tree from `init` (or a checkpoint).
          batch_size: fixed batch the artifact is compiled for.
          dtype: fixed input dtype (validated at call time, like shape).
          donate_params: donate param buffers to each call (see
            `CompiledNetwork`).
          lint: optional trace-lint gate (docs/lint.md) over the captured
            jaxpr/HLO/dispatch log.  "warn" emits a UserWarning listing
            any findings; "error" additionally raises
            `repro.analysis.lint.LintError` on error-severity findings.
            None (the default) skips linting.

        Returns a `CompiledNetwork`.  Raises ValueError for an unknown
        lint mode, and `LintError` under
        ``lint="error"`` when an error-severity finding survives.
        """
        if lint not in (None, "warn", "error"):
            raise ValueError(f"unknown lint mode {lint!r}; choose "
                             f"'warn', 'error' or None")
        cn = CompiledNetwork(self, params, batch_size, dtype=dtype,
                             donate_params=donate_params)
        if lint is not None:
            from repro.analysis.lint import LintError
            report = cn.lint()
            if lint == "error" and not report.ok:
                raise LintError(report)
            if report.findings:
                warnings.warn("trace-lint findings:\n" + report.format(),
                              stacklevel=2)
        return cn

    def compile_cache(self, params: dict,
                      buckets: Iterable[int] = (1, 2, 4, 8), *,
                      dtype=jnp.float32) -> "CompileCache":
        """Bucketed compilation cache for ragged serving traffic.

        Each bucket batch size lazily compiles its own `CompiledNetwork`
        (one jit trace per bucket, ever); `CompileCache.run(x)` pads a
        ragged batch up to the smallest bucket that fits and slices the
        real rows back out.  The serving frontend
        (`repro.serve.frontend.CNNServingEngine`) dispatches through this.
        """
        return CompileCache(self, params, buckets, dtype=dtype)


class CompiledNetwork:
    """Compile-once inference artifact for a planned Darknet `Network`.

    Holds the AOT-compiled executable for a fixed (batch_size, H, W, C)
    input, the bound params, and the engine's static op-dispatch plan
    (captured from the registry's trace-time counters during the single
    lowering).  Exposes `__call__`, `warmup()` and `profile()`.

    With ``donate_params=True`` the param buffers are donated to each call
    (the executable may alias them); the caller must then re-supply fresh
    params per call — use the default for a resident serving artifact.
    """

    def __init__(self, net: Network, params: dict, batch_size: int, *,
                 dtype=jnp.float32, donate_params: bool = False):
        self.net = net
        self.params = params
        self.batch_size = batch_size
        self.donate_params = donate_params
        h, w, c = net.in_shape
        self.in_spec = jax.ShapeDtypeStruct((batch_size, h, w, c), dtype)
        self._trace_count = 0

        def fwd(p, x):
            self._trace_count += 1  # python side-effect: counts traces only
            return net.apply(p, x)

        donate = (0,) if donate_params else ()
        before = backends.dispatch_counts()
        log_mark = backends.dispatch_log_size()
        # .trace() keeps the single-trace invariant while exposing the
        # closed jaxpr the trace linter walks; .lower().compile() on the
        # same Traced does not retrace.
        traced = (jax.jit(fwd, donate_argnums=donate)
                  .trace(params, self.in_spec))
        self._compiled = traced.lower().compile()
        self.closed_jaxpr = traced.jaxpr
        # The single trace just happened; the counter diff IS the network's
        # static engine-op plan (e.g. {('xla','conv2d'): n_conv_layers}),
        # and the log slice its per-dispatch detail (shapes/dtype/tiles —
        # the linter's R004 input).
        self.op_counts = backends.counts_since(before)
        self.op_log = tuple(backends.dispatch_log()[log_mark:])
        self.gemm_padded = backends.gemm_padded(self.op_log)
        self.im2col_phased = backends.im2col_phased(self.op_log)
        self.conv_implicit = backends.conv_implicit(self.op_log)
        self.conv_act = backends.conv_act(self.op_log)
        outs = jax.tree.leaves(traced.out_info)
        self.outputs = {"arrays": len(outs), "bytes_per_item": sum(
            math.prod(o.shape[1:]) * o.dtype.itemsize for o in outs)}

    @property
    def trace_count(self) -> int:
        return self._trace_count

    def hlo_text(self) -> str:
        """The compiled executable's optimized HLO (the text
        `analysis/hlo_cost` parses)."""
        return self._compiled.as_text()

    def lint(self, *, suppress=(), const_threshold: int | None = None):
        """Run the trace-lint rules (docs/lint.md) over this artifact's
        captured compile record — the closed jaxpr, the compiled HLO and
        the dispatch log; nothing retraces or recompiles.

        Args:
          suppress: suppression tokens, e.g. ("R005", "R002:scan").
          const_threshold: R005 byte threshold override.

        Returns a `repro.analysis.lint.LintReport`.
        """
        from repro.analysis import lint as lint_mod
        return lint_mod.lint_compiled_network(
            self, suppress=suppress, const_threshold=const_threshold)

    def __call__(self, x, params: dict | None = None):
        """Run the compiled executable on a batch.

        Args:
          x: input exactly matching the compiled (shape, dtype) spec.
          params: optional replacement param tree (required per call when
            compiled with donate_params=True).

        Returns the network output.  Raises ValueError when x's shape or
        dtype differs from the compiled spec — the artifact never
        retraces.
        """
        if x.shape != self.in_spec.shape:
            raise ValueError(f"compiled for input {self.in_spec.shape}, "
                             f"got {x.shape}")
        if jnp.dtype(x.dtype) != self.in_spec.dtype:
            raise ValueError(f"compiled for dtype {self.in_spec.dtype}, "
                             f"got {jnp.dtype(x.dtype)}")
        p = self.params if params is None else params
        return self._compiled(p, x)

    def warmup(self) -> "CompiledNetwork":
        """Run one call on zeros (device warm-up; compilation already done
        at construction).  Returns self for chaining."""
        jax.block_until_ready(
            self(jnp.zeros(self.in_spec.shape, self.in_spec.dtype)))
        return self

    def profile(self, x=None, reps: int = 3) -> dict:
        """Timed execution: per-call wall time plus the static engine
        op-dispatch counts captured at compile.

        Args:
          x: input batch (defaults to zeros of the compiled spec).
          reps: timed repetitions after one untimed warm call.

        Returns `{per_call_s, reps, batch_size, trace_count, op_counts,
        gemm_padded, im2col_phased, conv_implicit, conv_act, outputs,
        layers}`;
        ``gemm_padded`` says how many of the lowering's tiled GEMM
        dispatches pad an operand (`backends.gemm_padded`),
        ``im2col_phased`` how many of its convolutions read their patches
        from the input's phases (`backends.im2col_phased`),
        ``conv_implicit`` how many form their patches inside the GEMM
        (`backends.conv_implicit`), ``conv_act`` its convolutions by
        fused activation (`backends.conv_act`), ``outputs`` the output
        arrays and their bytes per batch row (``{arrays, bytes_per_item}``),
        ``layers`` the planned layers by kind.
        """
        if x is None:
            x = jnp.zeros(self.in_spec.shape, self.in_spec.dtype)
        jax.block_until_ready(self(x))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jax.block_until_ready(self(x))
        dt = (time.perf_counter() - t0) / reps
        del out
        return {"per_call_s": dt, "reps": reps,
                "batch_size": self.batch_size,
                "trace_count": self._trace_count,
                "op_counts": dict(self.op_counts),
                "gemm_padded": self.gemm_padded,
                "im2col_phased": self.im2col_phased,
                "conv_implicit": self.conv_implicit,
                "conv_act": dict(self.conv_act),
                "outputs": dict(self.outputs),
                "layers": dict(self.net.layer_counts)}


class CompileCache:
    """Keyed cache of `CompiledNetwork` executables for ragged batches.

    Buckets are the supported compiled batch sizes.  `run(x)` picks the
    smallest bucket >= len(x), zero-pads the batch up to it, dispatches ONE
    compiled call, and slices the real rows back — so a ragged request
    stream compiles each bucket exactly once (lazily, on first use) instead
    of once per distinct batch size.  Batches larger than the top bucket
    split into top-bucket chunks.

    Padding is sound because every planned layer is row-independent across
    the batch dim (conv/pool/connected/softmax/yolo all act per-image), so the
    real rows of a padded dispatch are bitwise identical to an exact-batch
    execution — tests/test_compile_cache.py asserts this.

    Observability: `hits`/`misses` count bucket-cache lookups, `stats()`
    reports traces, the per-bucket dispatch histogram, the pad-waste
    fraction (padded rows / total dispatched rows), ``gemm_padded``:
    the compiled buckets' tiled GEMM dispatches and how many of them pad
    an operand (`backends.gemm_padded`), ``im2col_phased``: their
    convolutions and how many read patches from the input's phases
    (`backends.im2col_phased`), ``conv_implicit``: their convolutions and
    how many form their patches inside the GEMM
    (`backends.conv_implicit`), ``conv_act``: their convolutions by fused
    activation (`backends.conv_act`), ``outputs``: the output arrays and
    their bytes per image (None before the first compile), and ``layers``:
    the planned layers by kind.
    """

    def __init__(self, net: Network, params: dict,
                 buckets: Iterable[int] = (1, 2, 4, 8), *,
                 dtype=jnp.float32):
        bs = tuple(sorted({int(b) for b in buckets}))
        if not bs or bs[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        self.net = net
        self.params = params
        self.buckets = bs
        self.dtype = jnp.dtype(dtype)
        self._compiled: dict[int, CompiledNetwork] = {}
        self.hits = 0
        self.misses = 0
        self._dispatches = collections.Counter()  # bucket -> n dispatches
        self._rows_real = 0
        self._rows_pad = 0

    def bucket_for(self, n: int) -> int | None:
        """Smallest bucket >= n, or None when n exceeds the top bucket."""
        for b in self.buckets:
            if b >= n:
                return b
        return None

    def get(self, bucket: int) -> CompiledNetwork:
        """The compiled executable for a bucket (lazy compile on miss).

        Raises ValueError when `bucket` is not one of the cache's buckets.
        """
        if bucket not in self.buckets:
            raise ValueError(f"{bucket} is not a bucket; have {self.buckets}")
        cn = self._compiled.get(bucket)
        if cn is None:
            self.misses += 1
            cn = self.net.compile(self.params, batch_size=bucket,
                                  dtype=self.dtype)
            self._compiled[bucket] = cn
        else:
            self.hits += 1
        return cn

    def run(self, x):
        """Dispatch a ragged batch: pad to bucket, one compiled call, slice.

        x: (n, H, W, C) with the cache dtype; n >= 1.  Batches above the top
        bucket are processed in top-bucket chunks and concatenated.

        Returns the (n, ...) network output for the real rows (a tuple of
        them for a multi-output network).  Raises ValueError on an empty
        batch or a dtype differing from the cache's compiled dtype.
        """
        n = x.shape[0]
        if n == 0:
            raise ValueError("empty batch")
        if jnp.dtype(x.dtype) != self.dtype:
            raise ValueError(f"cache compiled for dtype {self.dtype}, "
                             f"got {jnp.dtype(x.dtype)}")
        top = self.buckets[-1]
        if n > top:
            return jax.tree.map(
                lambda *ys: jnp.concatenate(ys, axis=0),
                *[self.run(x[i:i + top]) for i in range(0, n, top)])
        b = self.bucket_for(n)
        cn = self.get(b)
        xb = x if b == n else jnp.concatenate(
            [x, jnp.zeros((b - n,) + x.shape[1:], self.dtype)], axis=0)
        y = cn(xb)
        self._dispatches[b] += 1
        self._rows_real += n
        self._rows_pad += b - n
        if isinstance(y, tuple):
            return tuple(a[:n] for a in y)
        return y[:n]

    @property
    def trace_count(self) -> int:
        return sum(cn.trace_count for cn in self._compiled.values())

    def warmup(self) -> "CompileCache":
        """Eagerly compile + warm every bucket (otherwise lazy)."""
        for b in self.buckets:
            self.get(b).warmup()
        return self

    def _summed(self, counter: str) -> dict:
        """A `CompiledNetwork` dispatch-log counter (``gemm_padded``,
        ``im2col_phased``, ``conv_implicit``, ``conv_act``: `backends`
        functions of the same names) summed over the compiled buckets
        (read at compile time, so `stats()` stays cheap per step)."""
        out = getattr(backends, counter)(())
        for cn in self._compiled.values():
            for key, val in getattr(cn, counter).items():
                out[key] = out[key] + val if key in out else val
        return out

    def stats(self) -> dict:
        total = self._rows_real + self._rows_pad
        return {
            "buckets": self.buckets,
            "compiled": tuple(sorted(self._compiled)),
            "traces": self.trace_count,
            "hits": self.hits,
            "misses": self.misses,
            "dispatches": dict(self._dispatches),
            "rows_real": self._rows_real,
            "rows_padded": self._rows_pad,
            "pad_waste": (self._rows_pad / total) if total else 0.0,
            "gemm_padded": self._summed("gemm_padded"),
            "im2col_phased": self._summed("im2col_phased"),
            "conv_implicit": self._summed("conv_implicit"),
            "conv_act": self._summed("conv_act"),
            "outputs": next((dict(cn.outputs)
                             for cn in self._compiled.values()), None),
            "layers": dict(self.net.layer_counts),
        }
