"""prep_ms_p50: median host prep of a stack (the program's ``stack.prep``
span in ``repro.workloads.astro:stack_task``: the draw of the cutouts'
shifts, and for a stack of several files or of produced stacks the host
copy of their pixels too), over the stacks completed in the window."""
from bench.lib.spanread import span_p50


def read(rec):
    s = span_p50(rec, "stack.prep")
    return None if s is None else s * 1e3
