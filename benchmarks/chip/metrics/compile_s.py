"""Seconds JAX spent in backend compiles (persistent-cache loads
included) during set-up, from ``jax.monitoring``."""
UNIT = "s"
LAYER = "Session build: api/session.py, core/graph.py, core/sampler.py"
MOVES = "setup_s"


def read(run):
    return run.compile_setup_s
