"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a ``Trace``: per device, the ops of its "XLA Ops" line (short HLO
name, the jitted module that ran it, start, end, in ns) and the module
runs of its "XLA Modules" line; the harness's own host spans
(``run.Spans``, written as ``TraceAnnotation``s), and the runtime's
events on the same host thread (dispatch, transfers), all on one clock.
``reduce`` turns a ``Trace`` into:

  window_s        the traced window: the harness's ``window`` span
  busy_s          union of the intervals in which an op ran on a device,
                  inside the window, averaged over the devices
  idle_share      1 - busy_s / window_s
  op_s            device seconds by "module/op" (``spmm/spmm`` is the
                  Pallas call inside ``jit_spmm``), averaged over the
                  devices
  module_s        device seconds by jitted module (``jit_spmm`` ->
                  ``spmm``): the kernel's time, wrapper ops included
  gaps            idle device time inside the window, split by what
                  the host thread was in at that moment: the innermost
                  harness span, "/" the innermost runtime event, if any
                  ("(none)" outside every span): {label: [seconds,
                  count, longest]}, averaged over the devices
  collective_exposed_s
                  time in collective ops during which no other op ran on
                  that device, averaged over the devices

``load_json`` reads a ``Trace`` kept as plain JSON
(``dataclasses.asdict``), the form of the recorded trace the tests check
this module on.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVE = re.compile(r"all-reduce|all-gather|collective-permute|"
                        r"reduce-scatter|all-to-all|\bsend\b|\brecv\b")


@dataclasses.dataclass
class Trace:
    # device id -> [(op name, module, start_ns, end_ns)]
    ops: Dict[str, List[Tuple[str, str, float, float]]]
    # device id -> [(module, start_ns, end_ns)]
    modules: Dict[str, List[Tuple[str, float, float]]]
    # [(span name, start_ns, end_ns)]: the harness's spans
    spans: List[Tuple[str, float, float]]
    # [(event name, start_ns, end_ns)]: other events of their thread
    host: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)


def module_key(name: str) -> str:
    """``jit_spmm(123)`` / ``jit_spmm`` -> ``spmm``."""
    name = re.sub(r"\(.*\)$", "", name or "")
    return name[4:] if name.startswith("jit_") else name


def op_key(name: str) -> str:
    """``%spmm.1 = f32[...] custom-call(...)`` -> ``spmm``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _in_modules(rows, mods):
    """Label each op (name, start, end) with the module run that holds
    it (runs on one device do not overlap)."""
    starts = [s for _, s, _ in mods]
    out = []
    for name, s, e in rows:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][0] if i >= 0 and s < mods[i][2] else ""
        out.append((name, mod, s, e))
    return out


# ----------------------------------------------------------------------
# reading
# ----------------------------------------------------------------------

def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str, span_names: Sequence[str]) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    want = set(span_names)
    ops: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    spans: List = []
    host: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            rows, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    rows += [(op_key(e.name), e.start_ns, e.end_ns)
                             for e in line.events]
                elif line.name == "XLA Modules":
                    mods += [(module_key(e.name), e.start_ns, e.end_ns)
                             for e in line.events]
            if rows:
                mods.sort(key=lambda m: m[1])
                rows.sort(key=lambda r: r[1])
                ops[plane.name] = _in_modules(rows, mods)
                modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                mine = [ev for ev in evs if ev[0] in want]
                if mine:
                    spans += mine
                    host += [ev for ev in evs if ev[0] not in want]
    return Trace(ops=ops, modules=modules, spans=spans, host=host)


def load_json(path: str) -> Trace:
    with open(path) as f:
        d = json.load(f)
    return Trace(ops={k: [tuple(r) for r in v] for k, v in d["ops"].items()},
                 modules={k: [tuple(r) for r in v]
                          for k, v in d["modules"].items()},
                 spans=[tuple(s) for s in d["spans"]],
                 host=[tuple(s) for s in d.get("host", [])])


# ----------------------------------------------------------------------
# interval arithmetic
# ----------------------------------------------------------------------

def merge(ivs: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[List[float]] = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(ivs: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs
            if min(e, hi) > max(s, lo)]


def clip3(rows, lo: float, hi: float):
    """``clip`` for (name, start, end) rows."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in rows
            if min(e, hi) > max(s, lo)]


def length(ivs: Sequence[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def complement(merged: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def subtract(a: Sequence[Interval], b_merged: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the (merged) intervals ``a`` that no interval of
    ``b_merged`` covers."""
    out = []
    for s, e in merge(a):
        out += complement(clip(b_merged, s, e), s, e)
    return out


def innermost(spans: Sequence[Tuple[str, float, float]],
              cuts: Sequence[float]) -> List[str]:
    """For each cut point, the innermost span covering it ("" where none
    does).  Spans of one thread nest, so a stack sweep finds it."""
    order = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out, stack, i = [], [], 0
    for t in cuts:
        while i < len(order) and order[i][1] <= t:
            stack.append(order[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else "")
    return out


def label_segments(spans: Sequence[Tuple[str, float, float]],
                   lo: float, hi: float,
                   host: Sequence[Tuple[str, float, float]] = ()
                   ) -> List[Tuple[float, float, str]]:
    """Cut [lo, hi] at every span and host-event boundary; label each
    piece "<innermost span>/<innermost host event>" (the event part only
    where one covers it; "(none)" where no span does)."""
    cuts = sorted({lo, hi} | {t for _, s, e in list(spans) + list(host)
                              for t in (s, e) if lo < t < hi})
    mids = [(a + b) / 2 for a, b in zip(cuts, cuts[1:])]
    outer = innermost(spans, mids)
    inner = innermost(host, mids)
    out = []
    for (a, b), o, n in zip(zip(cuts, cuts[1:]), outer, inner):
        label = o or "(none)"
        if n:
            label = f"{label}/{n}"
        if out and out[-1][2] == label:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def attribute(gaps: Sequence[Interval],
              segments: Sequence[Tuple[float, float, str]]
              ) -> Dict[str, List[float]]:
    """{label: [seconds, count, longest]} of the gaps, each gap's time
    split over the labelled segments it crosses; a gap counts once, under
    the label that holds most of it."""
    starts = [s for s, _, _ in segments]
    out: Dict[str, List[float]] = {}
    for gs, ge in gaps:
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        share: Dict[str, float] = {}
        while i < len(segments) and segments[i][0] < ge:
            s, e, label = segments[i]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                share[label] = share.get(label, 0.0) + ov
            i += 1
        for label, sec in share.items():
            out.setdefault(label, [0.0, 0, 0.0])[0] += sec * 1e-9
        if share:
            top = max(share, key=share.get)
            rec = out[top]
            rec[1] += 1
            rec[2] = max(rec[2], (ge - gs) * 1e-9)
    return out


# ----------------------------------------------------------------------
# the reduction
# ----------------------------------------------------------------------

def window(trace: Trace) -> Interval:
    w = [(s, e) for name, s, e in trace.spans if name == "window"]
    if len(w) != 1:
        raise ValueError(f"expected one 'window' span, found {len(w)}")
    return w[0]


def reduce(trace: Trace) -> Dict:
    lo, hi = window(trace)
    ndev = max(len(trace.ops), 1)
    segments = label_segments(
        [s for s in trace.spans if s[0] != "window"], lo, hi, trace.host)
    busy = 0.0
    coll_exposed = 0.0
    op_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    gaps: Dict[str, List[float]] = {}
    for mods in trace.modules.values():
        for m, s, e in clip3(mods, lo, hi):
            module_s[m] = module_s.get(m, 0.0) + (e - s) * 1e-9 / ndev
    for rows in trace.ops.values():
        inside = [(n, m, max(s, lo), min(e, hi)) for n, m, s, e in rows
                  if min(e, hi) > max(s, lo)]
        merged = merge([(s, e) for _, _, s, e in inside])
        busy += length(merged)
        for n, m, s, e in inside:
            key = f"{m}/{n}"
            op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9 / ndev
        coll = [(s, e) for n, _, s, e in inside if COLLECTIVE.search(n)]
        if coll:
            other = merge([(s, e) for n, _, s, e in inside
                           if not COLLECTIVE.search(n)])
            coll_exposed += length(subtract(coll, other))
        for label, (sec, cnt, longest) in attribute(
                complement(merged, lo, hi), segments).items():
            g = gaps.setdefault(label, [0.0, 0, 0.0])
            g[0] += sec / ndev
            g[1] += cnt
            g[2] = max(g[2], longest)
    window_s = (hi - lo) * 1e-9
    busy_s = busy * 1e-9 / ndev
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "op_s": op_s, "module_s": module_s, "gaps": gaps,
            "collective_exposed_s": coll_exposed * 1e-9 / ndev,
            "n_devices": len(trace.ops)}


def breakdown(red: Dict, top: int = 10) -> Dict[str, List]:
    """The ``breakdown`` of a traced result line: the device ops that
    took most time, and the idle time by what the host was doing."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"].items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[f"{label} ({int(cnt)} gaps, longest "
                           f"{longest:.6f} s)", sec]
                          for label, (sec, cnt, longest) in gaps]}
