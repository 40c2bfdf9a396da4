"""device.idle_pct: the share of the traced window in which a device runs nothing.

1 - busy / window per device, where busy is the union of its operations'
intervals in the trace; the mean over the chips used.
"""


def read(rec):
    if not rec.trace or rec.trace_window_s <= 0:
        return None
    idle = [1.0 - d.busy_s / rec.trace_window_s for d in rec.trace]
    return 100.0 * sum(idle) / len(idle)
