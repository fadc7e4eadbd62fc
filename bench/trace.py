"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

What it reads: the device planes (``/device:TPU:<n>``) and, on each, the
line of XLA operations. From them:

* ``busy_s``: the union of the intervals in which any operation ran,
  averaged over the chips traced;
* ``kernel_s`` / ``kernel_calls``: device seconds and calls per Pallas
  kernel, found by the kernel's stable name (``bsr_spmm``, ``bsr_sddmm``,
  ``paged_flash_attention``, ...) in the operation's name or its HLO op /
  long name;
* ``device_ops``: the operations that took the most device time (self
  time: a ``while`` loop's own time excludes its body's ops), by HLO op
  name with XLA's numeric suffix removed;
* ``idle_gaps``: the longest spans with no operation on the device, each
  named by the benchmark's innermost host annotation (``bench.*``) open
  when the gap began.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

KERNELS = ("bsr_spmm_palette", "bsr_spmm", "bsr_sddmm",
           "paged_flash_attention")
_SUFFIX = re.compile(r"(\.(\d+|remat\d*|clone\d*))+$")


def op_name(event_name: str) -> str:
    """``%fusion.154 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def _self_times(events):
    """(event, self ns): an event's duration less the events nested in it
    on the same line (a ``while`` holds the ops of its body)."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        end = ev.start_ns + ev.duration_ns
        while stack and stack[-1][1] <= ev.start_ns:
            stack.pop()
        if stack and end <= stack[-1][1]:
            out[stack[-1][2]][1] -= ev.duration_ns
        out.append([ev, ev.duration_ns])
        stack.append((ev, end, len(out) - 1))
    return out


def _events(plane, line_names):
    for line in plane.lines:
        if line.name in line_names:
            for ev in line.events:
                yield ev


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def kernel_of(name: str) -> str | None:
    """The Pallas kernel an operation event is, if any: its stable
    ``kernel_name`` is the HLO op's name."""
    op = op_name(name)
    for k in KERNELS:
        if op == k:
            return k
    return None


def reduce_planes(planes, t0_ns=None, t1_ns=None) -> dict:
    """Numbers of one trace's planes (``ProfileData.planes``). ``t0_ns`` /
    ``t1_ns`` bound the window on the trace's clock when given."""
    devs = [p for p in planes if p.name.startswith("/device:TPU:")]
    busy, kern, kern_n, ops = [], {}, {}, {}
    dev_intervals = []
    for p in devs:
        evs = [ev for ev in _events(p, ("XLA Ops",))
               if t0_ns is None or (ev.start_ns + ev.duration_ns >= t0_ns
                                    and ev.start_ns <= t1_ns)]
        iv = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in evs]
        for ev, self_ns in _self_times(evs):
            k = kernel_of(ev.name)
            if k is not None:
                kern[k] = kern.get(k, 0.0) + ev.duration_ns * 1e-9
                kern_n[k] = kern_n.get(k, 0) + 1
            key = k or op_name(ev.name)
            ops[key] = ops.get(key, 0.0) + self_ns * 1e-9
        merged = _union(iv)
        dev_intervals.append(merged)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
    n = max(len(devs), 1)
    host = _host_spans(planes)
    gaps = []
    if dev_intervals and dev_intervals[0]:
        merged = dev_intervals[0]
        lo = t0_ns if t0_ns is not None else merged[0][0]
        hi = t1_ns if t1_ns is not None else merged[-1][1]
        edges = [(lo, merged[0][0])] + [
            (a[1], b[0]) for a, b in zip(merged, merged[1:])] + [
            (merged[-1][1], hi)]
        for s, e in edges:
            if e > s:
                gaps.append([_host_at(host, s), (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": sum(busy) / n, "n_devices": len(devs),
            "kernel_s": kern, "kernel_calls": kern_n,
            "device_ops": sorted(([k, v] for k, v in ops.items()),
                                 key=lambda kv: -kv[1]),
            "idle_gaps": gaps}


def _host_spans(planes):
    """(start, end, name) of the benchmark's host annotations."""
    spans = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    spans.sort()
    return spans


def _host_at(spans, t) -> str:
    """Innermost (latest-starting) annotation open at ``t``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best or "host (no annotation)"


def find_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes)


def reduce_dir(trace_dir, t0: float, t1: float) -> dict:
    """Reduce the newest trace under ``trace_dir``; the window is the host
    clock's ``t1 - t0`` (the trace runs from just before to just after)."""
    red = reduce_file(find_xplane(trace_dir))
    red["window_s"] = t1 - t0
    return red
