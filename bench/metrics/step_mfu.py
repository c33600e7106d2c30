"""Whole step's share of the chip's bf16 peak over the traced span: model
operations of acting and learning in the span (``bench/flops.py``), over
its seconds, over chips x peak. Moves ``consume_tps``."""

NAME, UNIT, LAYER, MOVES = "step_mfu", "%", "Whole step", "consume_tps"


def read(record: dict):
    from bench import flops
    c, peak, span = record["config"], record.get("peak"), record.get("traced")
    if not peak or not span or span["seconds"] <= 0:
        return None
    ops = (flops.per_env_step(c) * span["env_steps"]
           + flops.per_learner_sample(c) * span["learner_samples"])
    return 100.0 * ops / span["seconds"] / (record["chips"] * peak["bf16_flops"])
