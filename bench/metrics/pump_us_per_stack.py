"""pump_us_per_stack: time the dispatcher held the runtime lock in its pump
(``DispatchStats.lock_hold_s``) over the window, per task it dispatched."""


def read(rec):
    before, after = rec.dispatch_before, rec.dispatch_after
    n = after.get("dispatches", 0) - before.get("dispatches", 0)
    if n <= 0:
        return None
    return 1e6 * (after["lock_hold_s"] - before["lock_hold_s"]) / n
