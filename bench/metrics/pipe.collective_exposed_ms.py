"""pipe.collective_exposed_ms: collective time per step with no compute beside it.

Per device, the time in which a collective operation (collective-permute,
all-reduce, all-gather, reduce-scatter, all-to-all) runs and no other
operation does, from the device trace; the mean over devices, per traced
step. Only a cell whose step spans several chips has collectives to read.
"""


def read(rec):
    if rec.chips < 2 or not rec.trace or rec.steps_traced == 0:
        return None
    if not any(d.collective_s > 0 for d in rec.trace):
        return None
    exposed = sum(d.collective_exposed_s for d in rec.trace) / len(rec.trace)
    return exposed / rec.steps_traced * 1e3
