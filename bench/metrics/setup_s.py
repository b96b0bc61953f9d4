"""Seconds from the process's start to the window: simulation, engine, warm-up, compiles."""


def read(rec):
    return rec["setup_s"]
