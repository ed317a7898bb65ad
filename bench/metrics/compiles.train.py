"""Executables JAX built inside the training window, counted by the
program's ``launch.compile.CompileCounter``; every step after the first
should hit the first step's program."""
LAYER = "launchers (launch/compile.py)"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_tokens_per_s"


def read(run):
    return run.get("compiles")
