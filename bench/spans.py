"""The program's own spans and scopes, read from this run's profiler trace.

The readers of ``tick_host_ms.serve``, ``device_wait_host_ms.serve``,
``attention_ms.train`` and ``head_loss_ms.train`` (``bench/metrics/``) get
no trace path, so this module finds the run's trace itself: the newest
``.xplane.pb`` under ``.bench_out/trace/``. It is parsed once per process.

* **Clock alignment.** The device's clock and the host's differ by an
  offset of the order of a tick's idle gap. Each device ``XLA Modules``
  event is paired, by its flow id (``_c``), with the host's
  ``DoEnqueueProgram`` that launched it (``_p``) and the
  ``CompleteCallbacks`` that saw it finish (``_c``). The device cannot
  start a program before its launch nor finish it after its completion,
  so host = device + offset with offset at least launch start - module
  start and at most completion start - module end, for every module. The
  middle of that bound is used; its width is reported, and an idle gap
  narrower than the width stays unattributed.
* **Engine spans.** The engine's ``engine.*`` annotations on the host
  planes (``obs/trace.py``), grouped by tick: each ``engine.tick`` with a
  ``width`` (a tick that ran a step) and the phases it contains.
* **Scope join.** Each device op's ``tf_op`` (the HLO instruction's
  ``op_name`` metadata, held by the device plane's event metadata) keyed
  by program and instruction text; a fusion carries its root's. The
  innermost of the program's named scopes (``SCOPES``) on that path is the
  op's scope; ops under none go to ``(no scope)``.

The window, on the host clock, runs from the first to the last of the
benchmark's own annotations (``bench.*``), or over the whole trace where
there are none. A program without the spans or scopes (an older checkout)
reads as None, never as an error.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from pathlib import Path

from bench import trace as trace_lib

TRACE_ROOT = Path(__file__).resolve().parent.parent / ".bench_out" / "trace"
SCOPES = ("embed", "layers", "attention", "mlp", "head", "sample", "loss",
          "optimizer")
NO_SCOPE = "(no scope)"
TICK = "engine.tick"
SYNC = "engine.sync"
# the phases in which the host, not the device, holds the tick
HOST_PHASES = ("engine.schedule", "engine.prepare", "engine.dispatch",
               "engine.bookkeep")
_WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")
_PROGRAM = re.compile(r"\((\d+)\)$")


def current():
    """The ``Spans`` of the newest trace under ``TRACE_ROOT``, or None."""
    paths = glob.glob(str(TRACE_ROOT / "**" / "*.xplane.pb"), recursive=True)
    return load(max(paths, key=os.path.getmtime)) if paths else None


@functools.lru_cache(maxsize=2)
def load(path: str):
    from jax.profiler import ProfileData
    return Spans(ProfileData.from_file(path).planes, op_metadata(path))


def scope_of(op_path: str | None) -> str:
    """Innermost named scope on an ``op_name`` path: ``jit(f)/transpose(
    jvp(layers))/while/body/attention/dot`` -> ``attention``."""
    found = NO_SCOPE
    for part in (op_path or "").rsplit(":", 1)[0].split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in SCOPES:
            found = part
    return found


# -- the device plane's op metadata (not exposed by ProfileData) -----------

@functools.lru_cache(maxsize=1)
def _xspace_class():
    """A message class for the part of ``XSpace`` read here: planes, their
    event and stat metadata, and the metadata's stats. Other fields parse
    as unknown fields."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                           package="bench_xspace")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            fd = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                fd.type_name = f".bench_xspace.{tname}"

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msg("Stat", ("metadata_id", 1, F.TYPE_INT64, one, None),
        ("uint64_value", 3, F.TYPE_UINT64, one, None),
        ("int64_value", 4, F.TYPE_INT64, one, None),
        ("str_value", 5, F.TYPE_BYTES, one, None),
        ("ref_value", 7, F.TYPE_UINT64, one, None))
    msg("EventMeta", ("id", 1, F.TYPE_INT64, one, None),
        ("name", 2, F.TYPE_BYTES, one, None),
        ("stats", 5, F.TYPE_MESSAGE, many, "Stat"))
    msg("StatMeta", ("id", 1, F.TYPE_INT64, one, None),
        ("name", 2, F.TYPE_BYTES, one, None))
    msg("EventMetaEntry", ("key", 1, F.TYPE_INT64, one, None),
        ("value", 2, F.TYPE_MESSAGE, one, "EventMeta"))
    msg("StatMetaEntry", ("key", 1, F.TYPE_INT64, one, None),
        ("value", 2, F.TYPE_MESSAGE, one, "StatMeta"))
    msg("Plane", ("name", 2, F.TYPE_BYTES, one, None),
        ("event_metadata", 4, F.TYPE_MESSAGE, many, "EventMetaEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, many, "StatMetaEntry"))
    msg("Space", ("planes", 1, F.TYPE_MESSAGE, many, "Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.Space"))


def op_metadata(path: str) -> dict:
    """{(program id, op event name): tf_op} of the device planes."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(b"/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for e in plane.event_metadata:
            st = {stat_names.get(s.metadata_id): s for s in e.value.stats}
            prog, op = st.get(b"program_id"), st.get(b"tf_op")
            if prog is None or op is None:
                continue
            text = op.str_value or stat_names.get(op.ref_value, b"")
            pid = prog.uint64_value or prog.int64_value
            out[(pid, e.value.name.decode("utf-8", "replace"))] = \
                text.decode("utf-8", "replace")
    return out


# -- the reading ------------------------------------------------------------

def _cut(intervals, cover):
    """(ns of ``intervals`` inside ``cover``, what is left of them); both
    sorted and disjoint."""
    starts = [a for a, _ in cover]
    taken, left = 0, []
    for s, e in intervals:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        at = s
        for a, b in cover[i:]:
            if a >= e:
                break
            if b <= at:
                continue
            if a > at:
                left.append((at, a))
            taken += min(b, e) - max(a, at)
            at = min(b, e)
        if at < e:
            left.append((at, e))
    return taken, left


class Spans:
    """One trace's device modules and ops, engine spans and window."""

    def __init__(self, planes, metadata: dict):
        self.modules, self.ops = [], []   # device 0 only: one chip's story
        host, enqueue, complete = [], {}, {}
        planes = list(planes)
        devs = [p for p in planes if p.name.startswith("/device:TPU:")]
        for p in devs[:1]:
            for line in p.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        st = dict(ev.stats)
                        m = _PROGRAM.search(ev.name)
                        self.modules.append((
                            ev.start_ns, ev.start_ns + ev.duration_ns,
                            int(m.group(1)) if m else None,
                            st.get("_c", st.get("run_id"))))
                elif line.name == "XLA Ops":
                    self.ops = list(line.events)
        for p in planes:
            if not p.name.startswith("/host:"):
                continue
            for line in p.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(("engine.", "bench.")):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, name,
                                     dict(ev.stats)))
                    elif name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        st = dict(ev.stats)
                        key = st.get("_p" if name == "DoEnqueueProgram"
                                     else "_c", st.get("run_id"))
                        (enqueue if name == "DoEnqueueProgram"
                         else complete)[key] = ev.start_ns
        self.modules.sort(key=lambda m: m[0])
        host.sort(key=lambda h: (h[0], -h[1]))
        self.host = host
        self.metadata = metadata
        self.offset_ns, self.width_ns = self._align(enqueue, complete)
        off = self.offset_ns or 0
        edges = ([h[:2] for h in host if h[2].startswith("bench.")]
                 or [h[:2] for h in host]
                 + [(s + off, e + off) for s, e, _, _ in self.modules])
        self.window = (min(s for s, _ in edges), max(e for _, e in edges)) \
            if edges else (0, 0)

    def _align(self, enqueue, complete):
        lo, hi = None, None
        for s, e, _, flow in self.modules:
            if flow in enqueue:
                b = enqueue[flow] - s
                lo = b if lo is None else max(lo, b)
            if flow in complete:
                b = complete[flow] - e
                hi = b if hi is None else min(hi, b)
        if lo is None or hi is None or lo > hi:
            return None, None
        return (lo + hi) / 2, hi - lo

    # -- engine spans -------------------------------------------------------

    def ticks(self) -> list[dict]:
        """The window's ticks that ran a step (an ``engine.tick`` with a
        ``width``) and lie wholly inside it: start, end, args and
        {phase: ns} of the ``engine.*`` spans they contain."""
        t0, t1 = self.window
        out = []
        for s, e, name, st in self.host:
            if name != TICK or "width" not in st or s < t0 or e > t1:
                continue
            phases = {}
            for cs, ce, cname, _ in self.host:
                if cs >= s and ce <= e and cname.startswith("engine.") \
                        and cname != TICK:
                    phases[cname] = phases.get(cname, 0) + ce - cs
            out.append({"start": s, "end": e, "args": st, "phases": phases})
        return out

    # -- device idle time on the host clock -----------------------------------

    def idle_gaps(self) -> list[tuple[int, int]]:
        """(start, end) on the host clock of the window's spans with no op
        on the device; [] without an alignment."""
        if self.offset_ns is None:
            return []
        off = self.offset_ns
        busy = trace_lib._union(
            (ev.start_ns + off, ev.start_ns + ev.duration_ns + off)
            for ev in self.ops)
        t0, t1 = self.window
        gaps, at = [], t0
        for s, e in busy:
            if s > at:
                gaps.append((at, min(s, t1)))
            at = max(at, e)
            if at >= t1:
                break
        if at < t1:
            gaps.append((at, t1))
        return [(s, e) for s, e in gaps if e > s]

    def idle_by_span(self) -> dict:
        """Device idle ns of the window by the innermost host span it fell
        in: each ``engine.*`` phase, ``engine.tick`` for the tick's own
        time between phases, ``bench`` for the benchmark's own host work;
        ``unattributed`` for gaps narrower than the alignment's width or
        outside every span; ``total`` is all the window's idle time."""
        gaps = self.idle_gaps()
        out = {"total": sum(e - s for s, e in gaps)}
        spans = {}
        for s, e, name, _ in self.host:
            key = name if name.startswith("engine.") else "bench"
            spans.setdefault(key, []).append((s, e))
        left = [(s, e) for s, e in gaps if e - s >= self.width_ns]
        out["unattributed"] = out["total"] - sum(e - s for s, e in left)
        inner_first = sorted(spans, key=lambda k: (k in (TICK, "bench"),
                                                   k == "bench"))
        for key in inner_first:
            ns, left = _cut(left, trace_lib._union(spans[key]))
            if ns:
                out[key] = ns
        out["unattributed"] += sum(e - s for s, e in left)
        return out

    # -- scopes ---------------------------------------------------------------

    def scope_ms_per_run(self) -> dict:
        """{scope: device self ms per execution} of the program that took
        the most device time in the window (the step), its ops joined to
        their scope; {} where no op carries a scope."""
        starts = [m[0] for m in self.modules]
        t0, t1 = self.window
        off = self.offset_ns
        per_prog, runs = {}, {}
        for s, e, prog, _ in self.modules:
            if off is None or t0 <= s + off <= t1:
                runs[prog] = runs.get(prog, 0) + 1
        for ev, self_ns in trace_lib._self_times(self.ops):
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i < 0 or ev.start_ns >= self.modules[i][1]:
                continue
            s, _, prog, _ = self.modules[i]
            if off is not None and not t0 <= s + off <= t1:
                continue
            scope = scope_of(self.metadata.get((prog, ev.name)))
            d = per_prog.setdefault(prog, {})
            d[scope] = d.get(scope, 0) + self_ns
        if not per_prog:
            return {}
        step = max(per_prog, key=lambda p: sum(per_prog[p].values()))
        times = per_prog[step]
        if set(times) == {NO_SCOPE}:
            return {}
        return {k: v * 1e-6 / runs[step] for k, v in times.items()}
