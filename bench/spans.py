"""The program's own spans and scopes beside the device's operations, on
one clock.

``bench/trace.py`` reduces a trace to the device's operations and the
benchmark's ``bench.*`` host spans.  ``load`` here reads the same trace
and keeps, besides, what the program records of itself and what ties
the host's timeline to the device's:

* ``spans``: the program's host spans (``sched.*``, from
  ``repro.obs``) as ``[start_ns, end_ns, name]``;
* ``scopes``: per device, ``[start_ns, end_ns, scope, kind]`` of each
  operation under one of the ZeRO-1 step's named scopes (``zero1.*``,
  the innermost);
* ``modules``: per device, each execution of a compiled program
  (``XLA Modules``: ``jit_<fn>(<fingerprint>)``) as ``[start_ns, end_ns,
  fn, run_id]``;
* ``enqueues`` and ``completions``: the host's ``DoEnqueueProgram`` (the
  runtime hands an execution to a device) and ``CompleteCallbacks`` (it
  learns that the execution ended) as ``[start_ns, end_ns, device,
  run_id]``.

The host's and the device's timestamps come from two clocks, which the
profiler aligns to within about a millisecond.  ``clock_pieces`` bounds
the offset between them, piece by piece, from the executions whose run
id both sides carry; ``on_host_clock`` moves the device's events by
those offsets, and ``idle_gaps`` then puts each idle stretch of the
device down to the host spans it overlaps.  The readers at the end give
what the per-layer metrics planned for the program's spans
(``serve.step_idle_ms``, ``train.rs_ms``, ``train.ag_ms``) read.
"""
from __future__ import annotations

import bisect
import copy
import re

from bench import trace

MODULE = re.compile(r"^jit_(.+?)(?:\(-?\d+\))?$")
SCOPE = re.compile(r"(zero1\.[a-z_]+)/")
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"


def scope_of(op_name: str) -> str | None:
    """The innermost ``zero1.*`` scope of an HLO ``op_name`` path."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> innermost ``zero1.*`` scope, from a compiled
    module's HLO text (``metadata={op_name="..."}``)."""
    out = {}
    for m in re.finditer(r'%([\w.-]+) = [^\n]*?op_name="([^"]*)"',
                         hlo_text):
        s = scope_of(m.group(2))
        if s:
            out[m.group(1)] = s
    return out


def load(path: str, hlo: dict | None = None) -> dict:
    """``trace.load(path)`` plus ``spans``, ``scopes``, ``modules``,
    ``enqueues`` and ``completions`` (module docstring).  A TPU trace
    names an operation by its HLO instruction alone, so its scope comes
    from ``hlo`` (``{fn: hlo_scopes(compiled text of jit(fn))}``), for the
    operations that run inside an execution of that program."""
    from jax.profiler import ProfileData
    tr = trace.load(path)
    spans, modules = [], {}
    runs = {ENQUEUE: [], COMPLETE: []}
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"/device:(?:TPU|GPU):(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Modules":
                modules[int(m.group(1))] = [
                    [e.start_ns, e.start_ns + e.duration_ns,
                     _program(e.name), _stat(e, "run_id")]
                    for e in line.events]
            elif plane.name.startswith("/host:") and line.name:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith("sched."):
                        spans.append([e.start_ns, end, e.name])
                    elif e.name in runs:
                        runs[e.name].append(
                            [e.start_ns, end, _stat(e, "device_ordinal"),
                             _stat(e, "run_id")])
    scopes = {}
    for dev, ev in tr["devices"].items():
        inside = {fn: [[s, e] for s, e, p, _ in modules.get(dev, [])
                       if p == fn] for fn in hlo or {}}
        scopes[dev] = [
            [s, e, table[name], kind] for s, e, name, kind in ev
            for fn, table in (hlo or {}).items()
            if name in table and any(a <= s and e <= b
                                     for a, b in inside[fn])]
    tr.update(spans=sorted(spans), scopes=scopes, modules=modules,
              enqueues=sorted(runs[ENQUEUE]),
              completions=sorted(runs[COMPLETE]))
    return tr


def _program(name: str) -> str:
    m = MODULE.match(name)
    return m.group(1) if m else name


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return int(v)
    return None


# -- the shared clock ------------------------------------------------------

def clock_pieces(tr: dict, syncs=()) -> dict:
    """Per device, bounds on the offset that takes its times to the
    host's clock, piece by piece: ``{device: [[from_ns, lo_ns, hi_ns],
    ...]}`` in time order, a piece holding from its ``from_ns`` (device
    clock; None for the first) to the next piece's.  A bound the trace
    does not give is None.

    Lower bound: no execution starts on the device before the host began
    to enqueue it.  Upper bound: the host learns of an execution's end
    after it ends, and a host span named in ``syncs`` (one that waits for
    the result of the program the host enqueued last, as
    ``sched.sample`` waits for the argmax) ends after that program
    does.  Host and device events pair by run id.  The profiler aligns a
    device's clock to the host's anew now and then (on a v5e, steps of
    0.1-0.3 ms within a few seconds), so one offset need not hold over a
    whole trace.  A bound that contradicts the bracket of the piece
    before is held apart; a new piece starts at it once a second bound
    contradicts that bracket and agrees with the held one.  A held bound
    that a later bound contradicts while the piece before agrees (a stray
    sync, a mispaired run) is dropped, as is one still held at the end."""
    waits = sorted(e for _, e, name in tr["host"] + tr.get("spans", [])
                   if name in syncs)
    out = {}
    for dev, mods in sorted(tr.get("modules", {}).items()):
        run = {r: (s, e) for s, e, _, r in mods if r is not None}
        enq = sorted((s, r) for s, _, d, r in tr.get("enqueues", [])
                     if d == dev and r in run)
        # (device time, lower bound, upper bound)
        bounds = [(run[r][0], s - run[r][0], None) for s, r in enq]
        bounds += [(run[r][1], None, s - run[r][1])
                   for s, _, d, r in tr.get("completions", [])
                   if d == dev and r in run]
        starts = [s for s, _ in enq]
        for e in waits:
            i = bisect.bisect_left(starts, e)
            if i:
                end = run[enq[i - 1][1]][1]
                bounds.append((end, None, e - end))
        if not bounds:
            continue
        # ``kept``: the last piece's bracket as it stood before ``held``
        pieces, held, kept = [[None, None, None]], None, None
        for t, lo, hi in sorted(bounds, key=lambda b: b[0]):
            cur = pieces[-1]
            old = _narrow(cur, lo, hi)
            new = None if held is None else _narrow(held, lo, hi)
            if old is not None:
                cur[1:] = old
                if held is not None:
                    held = None if new is None else [held[0], *new]
            elif new is not None:
                cur[1:] = kept
                pieces.append([held[0], *new])
                held = None
            else:
                held, kept = [t, lo, hi], cur[1:]
        out[dev] = pieces
    return out


def _narrow(piece, lo, hi):
    """``piece``'s ``[lo, hi]`` narrowed by one more pair of bounds, or
    None where they contradict it."""
    nlo = lo if piece[1] is None else piece[1] if lo is None \
        else max(piece[1], lo)
    nhi = hi if piece[2] is None else piece[2] if hi is None \
        else min(piece[2], hi)
    if nlo is not None and nhi is not None and nlo > nhi:
        return None
    return [nlo, nhi]


def offset_of(piece) -> float:
    """The offset a piece applies: its bracket's midpoint, or its one
    bound where it has one."""
    _, lo, hi = piece
    if lo is None or hi is None:
        return lo if hi is None else hi
    return (lo + hi) / 2


def on_host_clock(tr: dict, pieces: dict) -> dict:
    """A copy of ``tr`` with each device time moved by the offset of the
    piece (``clock_pieces``) it falls in, by its start; a device without
    pieces stays as it is."""
    out = copy.copy(tr)

    def move(dev, rows):
        ps = pieces.get(dev)
        if not ps:
            return rows
        froms = [p[0] for p in ps[1:]]
        offs = [offset_of(p) for p in ps]
        moved = []
        for s, e, *r in rows:
            off = offs[bisect.bisect_right(froms, s)]
            moved.append([s + off, e + off, *r])
        return moved
    for key in ("devices", "scopes", "modules"):
        out[key] = {d: move(d, ev) for d, ev in tr.get(key, {}).items()}
    return out


# -- readings --------------------------------------------------------------

def idle_gaps(tr: dict, n: int = 10) -> list:
    """Idle device time (first device) by what the host was doing then,
    in the form of ``trace.idle_gaps``: each idle stretch is split over
    the host spans that overlap it, each part going to the innermost
    span covering it (a program span, else a benchmark span), else to
    ``host``.  Device times are taken as they are: move them onto the
    host's clock first (``on_host_clock``)."""
    devs = tr["devices"]
    if not devs:
        return []
    busy = trace.union(devs[min(devs)])
    lo, hi = tr["window"]
    if lo is None:
        lo = busy[0][0] if busy else 0
    if hi is None:
        hi = busy[-1][1] if busy else lo
    # program spans outrank benchmark spans; among spans of one rank the
    # one that started last covers least (spans of one thread nest)
    ranked = sorted([(1, s, e, nm) for s, e, nm in tr.get("spans", [])]
                    + [(0, s, e, nm) for s, e, nm in tr["host"]])
    tot = {}
    for gs, ge in trace.minus([[lo, hi]], busy):
        cuts = sorted({gs, ge} | {t for _, s, e, _ in ranked
                                   for t in (s, e) if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            name = "host"
            for _, s, e, nm in ranked:
                if s <= a and b <= e:
                    name = nm
            tot[name] = tot.get(name, 0.0) + (b - a)
    return [[k, v / 1e9] for k, v in sorted(tot.items(),
                                           key=lambda x: -x[1])[:n]]


def step_idle_ms(tr: dict) -> float | None:
    """Mean device idle time (first device) inside a traced decode step
    (a ``sched.step`` span holding a ``sched.decode``) less its
    admission (``sched.admit``), in ms."""
    devs = tr["devices"]
    sp = tr.get("spans", [])
    decodes = [s for s in sp if s[2] == "sched.decode"]
    steps = [s for s in sp if s[2] == "sched.step"
             and any(s[0] <= d[0] and d[1] <= s[1] for d in decodes)]
    if not devs or not steps:
        return None
    admits = [s for s in sp if s[2] == "sched.admit"]
    busy = trace.union(devs[min(devs)])
    idle = trace.length(trace.minus(trace.minus(steps, admits), busy))
    return idle / len(steps) / 1e6


def scope_ms(tr: dict, scope: str, steps: int) -> float | None:
    """Per step, the device time in which some operation under ``scope``
    runs, an asynchronous collective from its ``-start`` to its
    ``-done`` (the union of their spans), on the chip where it is
    longest."""
    sc = tr.get("scopes", {})
    if not any(sc.values()):
        return None
    return max(trace.length(mine + trace.collective_intervals(mine))
               for mine in ([x for x in ev if x[2] == scope]
                            for ev in sc.values())) / steps / 1e6
