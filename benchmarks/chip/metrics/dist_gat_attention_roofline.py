"""The distributed attention's (per-head scores and edge softmax in one
shard_map) share of its roofline, per chip: each chip's share of the
least time the window's logical ``gat_attention`` work could take
(``work.gat_attention`` from logical shapes, each call bound by FLOPs
or bytes, over the traced devices) over the module's device time in
the trace (``jit_dist_gat_attention``, averaged over the devices)."""
import work

UNIT = "%"
LAYER = "Collectives: core/primitives.py"
MOVES = "epoch_s"
MODULE = "dist_gat_attention"


def read(run):
    if run.unit != "epoch" or not run.traced:
        return None
    t = run.red["module_s"].get(MODULE)
    if not t:
        return None
    p = run.peaks
    least = work.least_s(run.calls, "gat_attention", p["flops_bf16"],
                         p["hbm_bytes_s"]) * run.units
    return 100.0 * least / run.red["n_devices"] / t
