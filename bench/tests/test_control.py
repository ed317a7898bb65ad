"""The control -- the plain reference in the precision below the one the
configuration states (fp8 operands for bfloat16), put in the program's
place -- comes out not correct, while the program does.  Run at sizes a
test can hold: the serve cell at its published widths with four layers,
the train cell scaled down; the readings at each cell's own size come
from ``bench/control.py`` on the chip (``PERF.md``)."""
import time

from bench import harness, serve

from test_faults import train_verdict


def test_serve_control_fails(mid_root, cpu_peaks):
    import jax
    spec = harness.cell_spec("mid.chat", mid_root)
    out = serve.run(spec, 5, 6.0, False, time.perf_counter(), jax.devices()[:1],
                    control=True)
    limit = spec["cell"]["limits"]["max_gap"]
    assert out["cmp"]["max_gap"] <= limit < out["cmp"]["control_gap"]


def test_train_control_fails(tiny_root):
    r = train_verdict(tiny_root, "control")
    assert not r["correct"], r["checks"]
