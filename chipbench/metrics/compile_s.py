"""Backend compile seconds during set-up (JAX monitoring events)."""
LAYER = "set-up (weights, warm-up, XLA compile)"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return run.setup_compile_s
