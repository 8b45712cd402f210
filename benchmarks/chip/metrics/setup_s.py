"""Seconds from process start to the window's start: Session build,
inputs, compiles or cache loads, and warm-up (host clock)."""
UNIT = "s"


def read(run):
    return run.setup_s
