"""Share of the serving engine's steps in which the device sat idle:
device idle inside the `engine.step` spans over their summed length.
Unlike `device_idle_share` it leaves out the time between steps (the
wait for the next arrival)."""


def read(record):
    from metrics.engine_spans import STEP, step_idle_share

    return step_idle_share(record, STEP)
