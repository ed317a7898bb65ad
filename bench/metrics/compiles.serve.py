"""Executables JAX built inside the serving window, counted by the
program's ``launch.compile.CompileCounter``.  A compile there stalls every
request of the batch for seconds; the warm-up is meant to leave none."""
LAYER = "launchers (launch/compile.py)"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(run):
    return run.get("compiles")
