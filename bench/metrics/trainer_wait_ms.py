"""Time per iteration the trainer is not busy (waiting on rollouts, the
boundary and the queue): the mean of ``wall_time - train_time`` over the
window's iterations."""


def read(rec):
    hist = rec.get("hist")
    if not hist:
        return None
    return 1e3 * sum(h["wall_time"] - h["train_time"]
                     for h in hist) / len(hist)
