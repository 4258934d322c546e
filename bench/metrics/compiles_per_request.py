"""Executors: compilations inside the window (JAX's backend compiles and
compile-cache reads, counted by the benchmark's own listener,
``Recorder.on_compile_event``) per request attempted."""


def read(run):
    if not run.reqs:
        return None
    return run.compiles / len(run.reqs)
