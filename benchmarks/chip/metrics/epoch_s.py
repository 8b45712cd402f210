"""Seconds per all-node epoch: the window's elapsed time through the
last completed epoch, over the number of epochs (host clock)."""
UNIT = "s"


def read(run):
    return run.unit_s if run.unit == "epoch" else None
