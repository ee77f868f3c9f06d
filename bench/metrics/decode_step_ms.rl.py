"""Device time of one rollout decode step: the device seconds of the paged
engine's decode program (module ``jit__decode_block``) in the traced
iterations, over the decode steps the engine counted there."""
from bench.trace_reduce import module_time

MODULE = "jit__decode_block"


def read(rec):
    red, steps = rec.get("trace"), rec.get("decode_steps_traced")
    if not red or not steps:
        return None
    secs, calls = module_time(red, MODULE)
    return 1e3 * secs / steps if calls else None
