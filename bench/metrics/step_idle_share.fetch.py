"""Device idle inside the `engine.fetch` spans (the blocking copy of a
chunk call's or decode step's logits to the host) over the summed length
of the `engine.step` spans: the part of `step_idle_share` spent waiting
on that copy."""


def read(record):
    from metrics.engine_spans import step_idle_share

    return step_idle_share(record, "engine.fetch")
