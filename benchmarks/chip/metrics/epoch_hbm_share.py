"""The epoch's share of the chip's HBM bandwidth: the least bytes one
epoch must move (the model file's ``epoch_min_bytes``) over the run's
seconds per epoch, over the peak bandwidth."""
UNIT = "%"
LAYER = "Model step: run_model over the whole epoch"
MOVES = "epoch_s"


def read(run):
    if run.unit != "epoch" or run.peaks is None:
        return None
    b = run.model.epoch_min_bytes(run.graphs, run.model_cfg)
    return 100.0 * b / run.unit_s / run.peaks["hbm_bytes_s"]
