"""Seconds per window iteration in ``PPOTrainer.generate_rollout``
(prefill and decode of the rollout), from the harness's span around it."""


def read(rec):
    v = rec["spans"].get("stage3.gen_s")
    return sum(v) / len(v) if v else None
