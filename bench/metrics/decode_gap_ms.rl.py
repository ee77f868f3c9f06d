"""Gap between rollout decode steps: the host seconds from the end of one
engine step to the start of the next while the engine holds rows (the
hand-over between the generator's driving threads). The program's
``IterationStats.metrics["decode_gap_s"]`` summed over the window's
iterations, over their ``decode_steps``."""

KEY = "decode_gap_s"


def read(rec):
    hist = rec.get("hist") or []
    steps = sum(h["metrics"].get("decode_steps", 0) for h in hist)
    if not steps:
        return None
    return 1e3 * sum(h["metrics"][KEY] for h in hist) / steps
