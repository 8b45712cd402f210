"""The epoch's share of the chip's bf16 peak: the algorithmic FLOPs of
one epoch (the model file's ``epoch_calls``, logical shapes) over the
run's seconds per epoch, over the peak."""
import work

UNIT = "%"
LAYER = "Model step: run_model over the whole epoch"
MOVES = "epoch_s"


def read(run):
    if run.unit != "epoch" or run.peaks is None:
        return None
    return (100.0 * work.epoch_flops(run.calls) / run.unit_s
            / run.peaks["flops_bf16"])
