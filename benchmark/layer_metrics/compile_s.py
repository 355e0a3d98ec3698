"""Backend compile seconds over set-up; on a warm cache this is the
cache-load time, which JAX reports under the same event."""


def read(run):
    return run.setup["backend_compile_s"]
