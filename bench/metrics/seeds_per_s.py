"""Training seeds completed per second of the window: batch x steps over
the wall time from the end of the first timed step to the end of the last."""


def read(run):
    t0, t1 = run.window_ns
    return run.config["batch_size"] * len(run.window_steps) / ((t1 - t0) / 1e9)
