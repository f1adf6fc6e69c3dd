"""Prompt tokens prefilled plus output tokens emitted inside the window,
over the window's seconds. Counted from the engine's public counters at
the window's close: the prompt tokens submitted less the backlog still
to prefill; the decode steps' resident rows plus a first token for every
request whose prefill completed."""


def read(record):
    from metrics.common import window

    r0, close, secs = window(record)
    run = record["run"]
    sub = run["arrivals"][:close["submitted"]]
    prompt = sum(len(a.prompt) for a in sub) - close["backlog"]
    decoded = (close["report"]["resident_slot_steps"]
               - r0["resident_slot_steps"])
    first = close["submitted"] - close["queue"] - close["lane"]["pending"]
    return (prompt + decoded + first) / secs
