"""Share of the traced epoch window in which the device sat idle while
the host dispatched the forward pass (``infer.forward``): the layer ops
one by one (``ops.gemm``, ``ops.spmm``, ``ops.attn_scores_softmax``,
``ops.attend``, ``ops.activation``, ...) and the interpreter's Python
between them.

The trace reduction labels an idle gap ``<harness span>/<innermost host
event>``, so a runtime event inside a program span hides the span.  A
runtime event counts under the one phase that issues it on the
single-chip Pallas path: here the jitted calls (``PjitFunction(*)``,
``ParseArguments``, ``PJRT_LoadedExecutable_Execute*``) and a plain
``DevicePut``, which comes from an op's dispatch (the staged uploads go
through ``shard_args``, counted as staging).  None where the trace
holds none of the program's spans (a program that does not write
them)."""
UNIT = "%"
LAYER = "Device under the spec interpreter: core/ops.py run_model"
MOVES = "epoch_s"
# the innermost host events counted: exact names, then name prefixes
NAMES = ("infer.forward", "ParseArguments", "DevicePut")
PREFIXES = ("ops.", "PjitFunction(", "PJRT_LoadedExecutable_Execute")
PROGRAM = ("session.", "infer.", "model.", "ops.")


def read(run):
    if run.unit != "epoch" or not run.traced:
        return None
    inner = [(label.split("/", 1)[1], g[0])
             for label, g in run.red["gaps"].items() if "/" in label]
    if not any(name.startswith(PROGRAM) for name, _ in inner):
        return None
    sec = sum(s for name, s in inner
              if name in NAMES or name.startswith(PREFIXES))
    return 100.0 * sec / run.red["window_s"]
