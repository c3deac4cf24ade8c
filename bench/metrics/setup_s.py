"""setup_s: process start to the end of the runtime warmup (store, engine, warmup)."""


def read(run):
    return run.setup_s
