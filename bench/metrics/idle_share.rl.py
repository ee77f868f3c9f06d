"""Share of the traced iterations in which no operation ran on the
device: 1 - busy / window, from the profiler trace."""


def read(rec):
    red = rec.get("trace")
    if not red or red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
