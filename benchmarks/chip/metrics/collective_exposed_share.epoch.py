"""Share of the traced epoch window in which a collective (all-to-all,
collective-permute, all-reduce, ...) ran on a device while no other op
ran there, averaged over the devices (``tracereduce.reduce``'s
``collective_exposed_s`` over ``window_s``).

``tracereduce.COLLECTIVE`` spells the all-to-all with hyphens, and a
TPU trace names the op ``all_to_all``: the GEMM's all-to-alls are not
counted until that pattern takes both spellings."""
UNIT = "%"
LAYER = "Collectives: core/primitives.py"
MOVES = "epoch_s"


def read(run):
    if run.unit != "epoch" or not run.traced:
        return None
    return 100.0 * run.red["collective_exposed_s"] / run.red["window_s"]
