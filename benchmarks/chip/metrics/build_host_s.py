"""Host seconds of the Session build's graph construction and layer-wise
sampling (``Session.timings`` construct_s + sample_s)."""
UNIT = "s"
LAYER = "Session build: api/session.py, core/graph.py, core/sampler.py"
MOVES = "setup_s"


def read(run):
    t = run.timings
    if "construct_s" not in t or "sample_s" not in t:
        return None
    return t["construct_s"] + t["sample_s"]
