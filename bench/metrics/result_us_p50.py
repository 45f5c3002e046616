"""result_us_p50: median wait for a stack's image (the program's
``stack.result`` span: ``np.asarray`` of the kernel's output, which waits
for the kernel and the copy back), over the stacks completed in the
window."""
from bench.lib.spanread import span_p50


def read(rec):
    s = span_p50(rec, "stack.result")
    return None if s is None else s * 1e6
