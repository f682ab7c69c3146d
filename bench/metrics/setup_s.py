"""Seconds from the process start to the window's start: dataset, plan,
cache materialization, warm-up, compiles and the first timed step."""


def read(run):
    return run.setup_s
