"""Seconds per window iteration in ``PPOTrainer.score_rollout`` (actor,
reference, critic and reward forwards, KL-shaped rewards, GAE), from the
harness's span around it."""


def read(rec):
    v = rec["spans"].get("stage3.score_s")
    return sum(v) / len(v) if v else None
