"""Compile cache: compilations JAX reported inside the window of a
serving cell (want 0)."""


def read(run):
    if run.config["kind"] != "serve":
        return None
    return run.counters["window_compiles"]
