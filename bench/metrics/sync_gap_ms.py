"""Weight-plane sync gap per iteration: the mean of the program's
``IterationStats.metrics["sync_gap"]`` (time the boundary blocked on the
weight flip) over the window's iterations."""


def read(rec):
    hist = rec.get("hist")
    if not hist:
        return None
    return 1e3 * sum(h["metrics"]["sync_gap"] for h in hist) / len(hist)
