"""resolve_us_p50: median time an executor takes to stage one input (the
program's ``task.resolve`` span in ``DiffusionRuntime._execute``: cache
lookup, peer peek or store read, admit and index update), over the inputs
of the stacks completed in the window."""
from bench.lib.spanread import span_p50


def read(rec):
    s = span_p50(rec, "task.resolve")
    return None if s is None else s * 1e6
