"""Device idle inside the `engine.sample` spans (host sampling, token
bookkeeping and retirement after each fetch) over the summed length of
the `engine.step` spans: the part of `step_idle_share` spent sampling
on the host."""


def read(record):
    from metrics.engine_spans import step_idle_share

    return step_idle_share(record, "engine.sample")
