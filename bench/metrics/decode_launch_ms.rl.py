"""Launch time of one rollout decode step: the host seconds of the engine's
device calls (page invalidation, uploads, the decode program's dispatch and
the start of its readback). The program's
``IterationStats.metrics["decode_launch_s"]`` summed over the window's
iterations, over their ``decode_steps``."""

KEY = "decode_launch_s"


def read(rec):
    hist = rec.get("hist") or []
    steps = sum(h["metrics"].get("decode_steps", 0) for h in hist)
    if not steps:
        return None
    return 1e3 * sum(h["metrics"][KEY] for h in hist) / steps
