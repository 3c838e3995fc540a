"""Process start to window start: backend start-up, weights, engine
build, compilation or its load from the cache, warm-up."""


def read(run):
    return run.setup_s
