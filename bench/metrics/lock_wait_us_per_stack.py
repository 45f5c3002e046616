"""lock_wait_us_per_stack: time threads spent asking for the runtime lock
before holding it (``DispatchStats.lock_wait_s``: pump, index updates,
attempt completions) over the window, per task dispatched."""


def read(rec):
    before, after = rec.dispatch_before, rec.dispatch_after
    n = after.get("dispatches", 0) - before.get("dispatches", 0)
    if n <= 0 or "lock_wait_s" not in after:
        return None
    return 1e6 * (after["lock_wait_s"] - before["lock_wait_s"]) / n
