"""From the JAX profiler's trace to numbers.

A traced window is reduced to plain lists right after it closes: per
device, its XLA operations as ``[start_ns, end_ns, name, kind]`` (the HLO
instruction's name and its opcode, or ``tpu_custom_call`` for a Mosaic
kernel), and on the host, the benchmark's own spans (``bench.*``
annotations) as ``[start_ns, end_ns, name]``.  Every per-layer metric
that reads the trace works on that reduced form, through the functions
below; ``bench/tests`` checks them on a small recorded trace.

Times are nanoseconds on the profiler's clock; the window is the span
between the moment tracing started and the moment it stopped.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
import time

#: Opcodes of the collective operations whose device time is "the
#: exchange": the circulant rounds are collective-permutes.
COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all)")


def op_of(text: str) -> tuple[str, str]:
    """(instruction name, kind) of one 'XLA Ops' event, whose name is the
    HLO instruction's text: ``%name = shape opcode(operands), attrs``."""
    name, _, rest = text.partition(" = ")
    m = re.search(r" ([a-z][a-z0-9-]*)\(", " " + rest)
    kind = m.group(1) if m else rest.split("(")[0]
    if 'custom_call_target="tpu_custom_call"' in rest:
        kind = "tpu_custom_call"
    return name.lstrip("%"), kind


@contextlib.contextmanager
def span(name: str):
    """A host span that lands in the profiler's trace when one is open."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def maybe_dir(on: bool):
    """A scratch directory for one trace under TMPDIR, removed after."""
    if not on:
        yield None
        return
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        yield d
    finally:
        shutil.rmtree(d, ignore_errors=True)


class Capture:
    """One traced window: ``Capture(dir)`` starts it, ``stop()`` ends it,
    ``reduce()`` reads it back."""

    def __init__(self, path: str):
        import jax
        self.path = path
        jax.profiler.start_trace(path)
        self.t_start = time.perf_counter()
        self.window_s = None

    def stop(self):
        import jax
        self.window_s = time.perf_counter() - self.t_start
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        files = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no trace written under {self.path}")
        out = load(files[0])
        out["window_s"] = self.window_s
        return out


def load(path: str) -> dict:
    """``{"devices": {id: [[start_ns, end_ns, name, kind], ...]}, "host":
    [[start_ns, end_ns, name], ...], "window": [lo_ns, hi_ns]}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    lo, hi = None, None
    for plane in pd.planes:
        m = re.match(r"/device:(?:TPU|GPU):(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                devices[int(m.group(1))] = [
                    [e.start_ns, e.start_ns + e.duration_ns, *op_of(e.name)]
                    for e in line.events]
            elif plane.name.startswith("/host:") and line.name:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.start_ns, e.start_ns + e.duration_ns,
                                     e.name])
                    elif "start_trace" in e.name:
                        lo = e.start_ns + e.duration_ns
                    elif "stop_trace" in e.name:
                        hi = e.start_ns
    return {"devices": devices, "host": sorted(host), "window": [lo, hi]}


# -- interval arithmetic ---------------------------------------------------

def union(iv) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(iv) -> float:
    return float(sum(e - s for s, e in union(iv)))


def minus(a, b) -> list:
    """Parts of the union of ``a`` that no interval of ``b`` covers."""
    out, b = [], union(b)
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def busy_in(ev, spans, less=()) -> float:
    """Device time (ns) in which some operation of ``ev`` runs inside the
    intervals ``spans`` and outside those of ``less``."""
    busy = union(ev)
    return length(busy) - length(minus(busy, minus(spans, less)))


def busy_s(tr: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    devs = tr["devices"]
    return sum(length(ev) for ev in devs.values()) / len(devs) / 1e9 \
        if devs else 0.0


def idle_share(tr: dict) -> float | None:
    if not tr["devices"] or not tr.get("window_s"):
        return None
    return 1.0 - busy_s(tr) / tr["window_s"]


def collective_intervals(ev) -> list:
    """Where the exchange runs: a synchronous collective's own span, and
    for an asynchronous one, from its ``-start`` to its ``-done``."""
    out, open_ = [], []
    for s, e, name, kind in sorted(ev):
        if not COLLECTIVE.search(kind):
            continue
        if kind.endswith("-start"):
            open_.append(s)
        elif kind.endswith("-done") and open_:
            out.append([open_.pop(0), e])
        else:
            out.append([s, e])
    return out


def exposed_collective_ns(ev) -> float:
    """Device time in which the exchange runs and no other operation
    does."""
    compute = [x for x in ev if not COLLECTIVE.search(x[3])]
    return float(sum(e - s for s, e in minus(collective_intervals(ev),
                                             compute)))


def kernel_ns(ev, kind: str) -> tuple[float, int]:
    """Summed device time and count of the operations of ``kind``."""
    hits = [e - s for s, e, _, k in ev if k == kind]
    return float(sum(hits)), len(hits)


def top_ops(tr: dict, n: int = 10) -> list:
    """The device operations that took most time (seconds, averaged over
    the devices), by instruction name with its instance number dropped."""
    tot = {}
    for ev in tr["devices"].values():
        for s, e, name, _ in ev:
            key = re.sub(r"\.\d+$", "", name)
            tot[key] = tot.get(key, 0.0) + (e - s)
    k = max(len(tr["devices"]), 1)
    return [[name, ns / k / 1e9] for name, ns in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(tr: dict, n: int = 10) -> list:
    """Idle device time (first device) by what the host was doing then:
    the innermost benchmark span covering the gap, else ``host``."""
    devs = tr["devices"]
    if not devs:
        return []
    ev = devs[min(devs)]
    lo, hi = tr["window"]
    busy = union(ev)
    if lo is None:
        lo = busy[0][0] if busy else 0
    if hi is None:
        hi = busy[-1][1] if busy else lo
    gaps = minus([[lo, hi]], busy)
    tot = {}
    for s, e in gaps:
        name = "host"
        for hs, he, hn in tr["host"]:
            if hs <= s and e <= he:
                name = hn
        tot[name] = tot.get(name, 0.0) + (e - s)
    return [[k, v / 1e9] for k, v in sorted(tot.items(),
                                           key=lambda x: -x[1])[:n]]


def breakdown(tr: dict) -> dict:
    return {"device_ops": top_ops(tr), "idle_gaps": idle_gaps(tr)}
