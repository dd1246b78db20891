"""Seconds per window iteration in ``PPOTrainer.train_rlhf`` (actor step,
critic step, EMA update), from the harness's span around it."""


def read(rec):
    v = rec["spans"].get("stage3.train_s")
    return sum(v) / len(v) if v else None
