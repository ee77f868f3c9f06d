"""Host time of one rollout decode step: the engine's admission, the build
of the block's host arrays with its page allocation, and the commit of the
sampled tokens, prefill left out. The program's
``IterationStats.metrics["decode_host_s"]`` summed over the window's
iterations, over their ``decode_steps``."""

KEY = "decode_host_s"


def read(rec):
    hist = rec.get("hist") or []
    steps = sum(h["metrics"].get("decode_steps", 0) for h in hist)
    if not steps:
        return None
    return 1e3 * sum(h["metrics"][KEY] for h in hist) / steps
