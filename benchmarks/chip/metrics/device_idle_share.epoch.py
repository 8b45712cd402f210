"""Share of the traced epoch window in which no op ran on the device
(1 - busy union / window), averaged over the chips."""
UNIT = "%"
LAYER = "Device under the spec interpreter: core/ops.py run_model"
MOVES = "epoch_s"


def read(run):
    if run.unit != "epoch" or not run.traced:
        return None
    return 100.0 * run.red["idle_share"]
