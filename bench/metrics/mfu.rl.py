"""Whole-step utilisation of the RL window: the FLOPs the window's
iterations require (``bench/kinds/grpo.py window_work``: training 8N per
token with each group's prompt once, rollout 2N per prompt token per group
and per generated token, attention over each token's context) over the
window's length, the chips and the chip's bf16 peak."""


def read(rec):
    work, peak = rec.get("work"), rec.get("peak")
    if not work or not peak or rec["window_s"] <= 0:
        return None
    total = work["train_flops"] + work["rollout_flops"]
    return 100.0 * total / rec["window_s"] / (
        rec["chips"] * peak["bf16_flops_per_s"])
