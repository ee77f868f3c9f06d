"""Wait of one rollout decode step: the host seconds the engine blocks on
the readback of the block's sampled tokens, which holds the device's
compute of the step. The program's
``IterationStats.metrics["decode_wait_s"]`` summed over the window's
iterations, over their ``decode_steps``."""

KEY = "decode_wait_s"


def read(rec):
    hist = rec.get("hist") or []
    steps = sum(h["metrics"].get("decode_steps", 0) for h in hist)
    if not steps:
        return None
    return 1e3 * sum(h["metrics"][KEY] for h in hist) / steps
