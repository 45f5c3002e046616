"""setup_s: process start to the window's start (host clock): interpreter
and JAX start-up, compiling or loading programs, making the catalog,
starting the runtime or spawning the fleet with its store replicas, and the
warm-up stacks."""


def read(rec):
    return rec.setup_s
