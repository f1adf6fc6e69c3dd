"""Share of the token positions the chunk calls computed in the window
that held a prompt token: the window's change of the engine's
`chunk_prompt_tokens` over that of its `chunk_token_slots` (report()).
The rest is lane rows and bucket columns computed with no prompt token
in them. None where the engine counts neither."""

KEYS = ("chunk_prompt_tokens", "chunk_token_slots")


def read(record):
    from metrics.common import window

    r0, close, _ = window(record)
    r1 = close["report"]
    if not all(k in r0 and k in r1 for k in KEYS):
        return None
    slots = r1["chunk_token_slots"] - r0["chunk_token_slots"]
    if slots <= 0:
        return None
    return 100.0 * (r1["chunk_prompt_tokens"]
                    - r0["chunk_prompt_tokens"]) / slots
