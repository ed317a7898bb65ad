"""The decode steps' share of the chip's roofline: the least time the
work they require could take on the chip (``bench/work.py``: weights read
once, the live keys and values of the active slots, their FLOPs; the
larger of the compute and the memory bound) over the chip's busy time
while they ran.  From the trace of the window's last seconds: each
``bench.step`` span there is one step of the window (the last ones, in
order); the device's busy time inside those spans, less the spans of
their admissions (``bench.admit``), is the decode's.  Host time in which
the chip idles is not in it (``device.idle.serve`` reads that)."""
from bench import trace

LAYER = "model step (models/transformer.py via serve/engine.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"]:
        return None
    work, s, peak = run["work"], run["sizes"], run["peaks"]
    spans = [h for h in tr["host"] if h[2] == "bench.step"]
    admits = [h for h in tr["host"] if h[2] == "bench.admit"]
    if not spans or len(spans) > len(run["steps"]):
        return None
    steps = run["steps"][len(run["steps"]) - len(spans):]
    need, inside = 0.0, []
    for span, (_, _, _, active, keys, _) in zip(spans, steps):
        if active:
            need += work.roofline_s(*work.decode_step(s, active, keys), peak)
            inside.append(span)
    ev = tr["devices"][min(tr["devices"])]
    spent = trace.busy_in(ev, inside, admits) / 1e9
    return 100.0 * need / spent if spent else None
