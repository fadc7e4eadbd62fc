"""Profiling hooks: kernel/tick wall timers + the training telemetry stream.

Three tools, all off by default and free when off:

* **``Profiler``** — ``block_until_ready``-bracketed wall timers. The
  engine wraps its jitted mixed step (``engine/tick_step``) and every
  Pallas kernel entry point routes through ``kernel_call(name, fn, ...)``:
  when a profiler is active and the call is *eager* (concrete arrays), the
  call is timed end-to-end including device sync; when the call happens
  inside a ``jit`` trace (arguments are tracers — wall time there is
  meaningless), only a traced-invocation count is recorded. When no
  profiler is active the hook is one module-global load and a ``None``
  check. ``jax_trace_dir`` additionally brackets the run with
  ``jax.profiler.start_trace``/``stop_trace`` for a full XLA timeline.
* **``TrainTelemetry``** — a per-step JSONL stream for the training loop:
  loss / grad-norm metrics, the group-l1 penalty, live per-layer block
  sparsity on the serving BCSR grid, and debias progress — the paper's
  compression-trajectory figure as replayable data
  (``launch/train --telemetry-out run.jsonl``).
* **Sparsity/penalty helpers** — ``layer_block_sparsity`` /
  ``group_l1_penalty`` measure a dense param tree on the exact (out, in)
  block grid ``sparse.compress`` serves from, so the telemetry stream
  reports the sparsity the compressed checkpoint will actually have.
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, Optional

import numpy as np

import jax
from jax.core import Tracer as _Tracer

# the active profiler the kernel hooks consult — None = zero-overhead path
_ACTIVE: Optional["Profiler"] = None


def active() -> Optional["Profiler"]:
    return _ACTIVE


def kernel_call(name: str, fn: Callable, *args, **kwargs):
    """The kernel entry hook: ``ops.py`` wrappers route their jitted
    callable through this. Disabled cost: one global load + None check."""
    p = _ACTIVE
    if p is None:
        return fn(*args, **kwargs)
    return p.call(name, fn, *args, **kwargs)


class Profiler:
    """Wall-clock profiler for jitted entry points.

    Use as a context manager (``with Profiler() as p: ...; p.summary()``)
    or via explicit ``start()``/``stop()``. Only one profiler is active at
    a time (the kernel hooks consult a module global)."""

    def __init__(self, jax_trace_dir: Optional[str] = None):
        self.records: dict[str, dict] = {}
        self.jax_trace_dir = jax_trace_dir
        self._tracing = False

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "Profiler":
        global _ACTIVE
        _ACTIVE = self
        if self.jax_trace_dir:
            # a trace that was asked for and cannot start is an error: a run
            # without it would look traced and hold no device timeline
            try:
                jax.profiler.start_trace(self.jax_trace_dir)
            except Exception as e:
                _ACTIVE = None
                raise RuntimeError(
                    f"jax.profiler.start_trace({self.jax_trace_dir!r}) "
                    f"failed: {e}") from e
            self._tracing = True
        return self

    def stop(self) -> None:
        global _ACTIVE
        if _ACTIVE is self:
            _ACTIVE = None
        if self._tracing:
            try:
                jax.profiler.stop_trace()
            finally:
                self._tracing = False

    def __enter__(self) -> "Profiler":
        return self.start()

    def __exit__(self, *a) -> bool:
        self.stop()
        return False

    # -- measurement --------------------------------------------------------

    def _rec(self, name: str) -> dict:
        r = self.records.get(name)
        if r is None:
            r = self.records[name] = {"n_calls": 0, "total_ms": 0.0,
                                      "max_ms": 0.0, "n_traced": 0}
        return r

    def call(self, name: str, fn: Callable, *args, **kwargs):
        """Run ``fn(*args, **kwargs)``; eager calls are timed with a
        ``block_until_ready`` bracket, traced calls (inside jit) are only
        counted — a wall clock inside a trace measures tracing, not
        compute."""
        if any(isinstance(x, _Tracer)
               for x in jax.tree_util.tree_leaves((args, kwargs))):
            self._rec(name)["n_traced"] += 1
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        dt_ms = (time.perf_counter() - t0) * 1e3
        r = self._rec(name)
        r["n_calls"] += 1
        r["total_ms"] += dt_ms
        if dt_ms > r["max_ms"]:
            r["max_ms"] = dt_ms
        return out

    def summary(self) -> dict:
        """``{name: {n_calls, total_ms, mean_ms, max_ms, n_traced}}``."""
        out = {}
        for name, r in self.records.items():
            out[name] = dict(r, mean_ms=(r["total_ms"] / r["n_calls"]
                                         if r["n_calls"] else 0.0))
        return out

    def format_summary(self) -> str:
        lines = ["profile (wall, block_until_ready-bracketed):"]
        for name, r in sorted(self.summary().items(),
                              key=lambda kv: -kv[1]["total_ms"]):
            lines.append(
                f"  {name:<28} {r['n_calls']:>6} calls "
                f"{r['total_ms']:>9.1f} ms total {r['mean_ms']:>8.3f} ms/call"
                + (f" ({r['n_traced']} traced)" if r["n_traced"] else ""))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# training telemetry stream
# ---------------------------------------------------------------------------

class TrainTelemetry:
    """Append-only JSONL stream of training telemetry records.

    ``emit(record)`` writes one line and flushes — a crash loses at most
    the in-flight step, and the stream is tail-able while training runs."""

    def __init__(self, path: str):
        self.path = path
        self._f = None
        self.n_records = 0

    def emit(self, record: dict) -> None:
        if self._f is None:
            self._f = open(self.path, "w")
        self._f.write(json.dumps(record, default=_json_default) + "\n")
        self._f.flush()
        self.n_records += 1

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


def _json_default(x):
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    if hasattr(x, "item") and getattr(x, "ndim", None) == 0:
        return x.item()
    return float(x)


# ---------------------------------------------------------------------------
# block-sparsity / group-l1 measurement on the serving grid
# ---------------------------------------------------------------------------

def _iter_target_mats(params):
    """Yield ``(path, 2D (out, in) float64 matrix)`` for every compressible
    weight, walking the same targets on the same orientation as
    ``sparse.compress`` (stack axes — scanned layers, MoE experts — are
    averaged by yielding each slice)."""
    from repro.sparse.compress import (_LAYER_TARGETS, _as_out_in,
                                       _lead_axes)

    def per_layer(layer, path, stacked):
        for sub, names in _LAYER_TARGETS.items():
            if sub not in layer:
                continue
            for name in names:
                if name not in layer[sub]:
                    continue
                arr = np.asarray(layer[sub][name])
                p = f"{path}/{sub}/{name}"
                lead = _lead_axes(name, stacked)
                mats = (arr.reshape((-1,) + arr.shape[lead:]) if lead
                        else arr[None])
                for mat in mats:
                    view = _as_out_in(p, mat)
                    if view is not None:
                        yield p, view.astype(np.float64)

    for lkey, layer in (params.get("layers") or {}).items():
        yield from per_layer(layer, f"layers/{lkey}", stacked=True)
    for lkey, layer in (params.get("rem") or {}).items():
        yield from per_layer(layer, f"rem/{lkey}", stacked=False)
    if "head" in params:
        view = _as_out_in("head", np.asarray(params["head"]))
        if view is not None:
            yield "head", view.astype(np.float64)


def _block_norms(mat: np.ndarray, block: tuple) -> np.ndarray:
    br, bc = block
    r, c = mat.shape
    mp = np.pad(mat, ((0, (-r) % br), (0, (-c) % bc)))
    R, C = mp.shape[0] // br, mp.shape[1] // bc
    blocks = mp.reshape(R, br, C, bc).transpose(0, 2, 1, 3)
    return np.sqrt((blocks ** 2).sum(axis=(2, 3)))


def layer_block_sparsity(params, block: tuple = (8, 64)) -> dict:
    """Per-layer fraction of exactly-zero (br, bc) blocks on the serving
    (out, in) grid — the live SpC trajectory. Stacked layers aggregate
    over the stack axis."""
    zero: dict[str, int] = {}
    total: dict[str, int] = {}
    for path, mat in _iter_target_mats(params):
        norms = _block_norms(mat, block)
        zero[path] = zero.get(path, 0) + int((norms == 0.0).sum())
        total[path] = total.get(path, 0) + int(norms.size)
    return {p: zero[p] / max(total[p], 1) for p in total}


def total_block_sparsity(params, block: tuple = (8, 64)) -> float:
    zero = tot = 0
    for path, mat in _iter_target_mats(params):
        norms = _block_norms(mat, block)
        zero += int((norms == 0.0).sum())
        tot += int(norms.size)
    return zero / max(tot, 1)


def group_l1_penalty(params, block: tuple = (8, 64),
                     lam: float = 1.0) -> float:
    """``lam * sum ||block||_2`` over the plan grid — the regularizer term
    the SpC prox descends on, measured on the live params."""
    total = 0.0
    for _, mat in _iter_target_mats(params):
        total += float(_block_norms(mat, block).sum())
    return lam * total


def sparsity_telemetry_fn(block: tuple, lam: float = 1.0):
    """An ``extra_fn`` for ``train_loop`` telemetry: total + per-layer
    block sparsity on the serving grid and the group-l1 penalty (at
    ``lam``) — the paper's compression trajectory, one record per log
    step."""
    def fn(params):
        return {"block_sparsity": total_block_sparsity(params, block),
                "group_l1_penalty": group_l1_penalty(params, block, lam),
                "layer_block_sparsity": layer_block_sparsity(params, block)}
    return fn
