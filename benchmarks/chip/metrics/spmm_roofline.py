"""The spmm kernel's share of its roofline: the least time the window's
spmm calls could take (``work.spmm`` from logical shapes, each call
bound by FLOPs or bytes) over their device time in the trace (module
``jit_spmm``)."""
import work

UNIT = "%"
LAYER = "Kernels: kernels/spmm.py, kernels/gat_attention.py"
MOVES = "epoch_s"
KERNEL = "spmm"


def read(run):
    if run.unit != "epoch" or not run.traced:
        return None
    t = run.red["module_s"].get(KERNEL)
    if not t:
        return None
    p = run.peaks
    least = work.least_s(run.calls, KERNEL, p["flops_bf16"],
                         p["hbm_bytes_s"]) * run.units
    return 100.0 * least / t
