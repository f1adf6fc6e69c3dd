"""XLA backend compiles inside the window (JAX's compile-duration
event; a persistent-cache hit is not one). Should be 0."""


def read(record):
    return record["run"]["close"]["compiles"]
