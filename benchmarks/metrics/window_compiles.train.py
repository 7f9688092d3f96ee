"""Compile cache: compilations JAX reported inside the window of a
training cell (want 0)."""


def read(run):
    if run.config["kind"] != "train":
        return None
    return run.counters["window_compiles"]
