"""Share of decode slot-steps in the window that held a request: the
window's change of the engine's `resident_slot_steps` over its
`slot_steps` (report())."""


def read(record):
    from metrics.common import window

    r0, close, _ = window(record)
    slots = close["report"]["slot_steps"] - r0["slot_steps"]
    if slots <= 0:
        return None
    return 100.0 * (close["report"]["resident_slot_steps"]
                    - r0["resident_slot_steps"]) / slots
