"""launch_us_p50: median call of the stacking kernel on a stack's host
arrays (the program's ``stack.launch`` span: the jitted call copies the
cutouts and parameters to the device and launches the kernel), over the
stacks completed in the window."""
from bench.lib.spanread import span_p50


def read(rec):
    s = span_p50(rec, "stack.launch")
    return None if s is None else s * 1e6
