"""Seconds of the engine's construction with `pretune=True`: the
autotuner ranking and verifying every GEMM shape of its fleet."""


def read(record):
    return record["pretune_s"]
