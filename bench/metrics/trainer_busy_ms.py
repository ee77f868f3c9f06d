"""Trainer busy time per iteration: the mean of the program's
``IterationStats.train_time`` (host clock around the blocking grad steps
and the update) over the window's iterations."""


def read(rec):
    hist = rec.get("hist")
    if not hist:
        return None
    return 1e3 * sum(h["train_time"] for h in hist) / len(hist)
