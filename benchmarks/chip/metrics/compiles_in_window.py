"""Programs JAX compiled (or fetched from its persistent cache) inside
the window: compiles users pay for."""


def read(run):
    if run.probes is None:
        return None
    return run.probes.compile_counts(run.t0, run.t0 + run.seconds)["compiles"]
