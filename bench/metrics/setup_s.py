"""Seconds from the process's start to the window's opening: imports,
predictor, weights, engine with its pretune, and the warm-up of every
program the window can use (their compiles in a run that compiles)."""


def read(record):
    return record["setup_s"]
