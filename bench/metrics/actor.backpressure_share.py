"""Share of the actors' hand-offs to the replay that found the shard's
add queue full: the shards' ``add_backpressure`` counter over it plus
``blocks_added``, both taken over the window. Moves ``generate_tps``."""

NAME, UNIT, LAYER, MOVES = "actor.backpressure_share", "%", "Actors to replay", "generate_tps"


def read(record: dict):
    start, end = record.get("shards_start"), record.get("shards_end")
    if not start or not end:
        return None
    blocked = sum(e["backpressure"] - s["backpressure"] for s, e in zip(start, end))
    added = sum(e["blocks_added"] - s["blocks_added"] for s, e in zip(start, end))
    if blocked + added == 0:
        return None
    return 100.0 * blocked / (blocked + added)
