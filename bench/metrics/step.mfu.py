"""step.mfu: the whole step's share of the chip's peak while the device is busy.

The operations the traced steps need (``bench/flops``) over the peak bf16
rate times the device's busy time (the union of its operations' intervals),
summed over the chips used. It bounds every kernel's roofline share from
the step's side: a kernel taken off the path leaves its own share silent,
and this one still counts the whole step.
"""


def read(rec):
    busy = sum(d.busy_s for d in rec.trace) if rec.trace else 0.0
    if busy <= 0 or rec.steps_traced == 0:
        return None
    work = rec.flops_per_token * rec.tokens_traced
    return 100.0 * work / (rec.peaks["bf16_flops_per_s"] * busy)
