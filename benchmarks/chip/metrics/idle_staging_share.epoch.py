"""Share of the traced epoch window in which the device sat idle while
the host staged the epoch's inputs: the graph bindings (``infer.bind``:
neighbor ids and masks uploaded per layer), the mean edge weights
(``infer.mean_w``: computed on the host, then uploaded) and the feature
upload (``model.prepare``).

The trace reduction labels an idle gap ``<harness span>/<innermost host
event>``, so a runtime event inside a program span hides the span.  A
runtime event counts under the one phase that issues it on the
single-chip Pallas path: here the array uploads (``shard_args`` and the
``DevicePutWithSharding`` inside it).  The plain ``DevicePut`` is not
among them: it comes from an op's dispatch.  None where the trace
holds none of the program's spans (a program that does not write
them)."""
UNIT = "%"
LAYER = "Epoch host path: api/session.py infer_all"
MOVES = "epoch_s"
# the innermost host events counted: exact names, then name prefixes
NAMES = ("infer.bind", "infer.mean_w", "model.prepare", "shard_args",
         "DevicePutWithSharding")
PREFIXES = ()
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
