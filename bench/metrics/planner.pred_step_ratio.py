"""planner.pred_step_ratio: the planner's Eq. 14 step time over the measured one.

The program's cost model (``core.latency.total_latency``) prices the cell as
it runs: a profile of the model at the cell's sequence length, one stage per
device group of ``core.network.tpu_stage_network``, the runtime's equal
layer blocks (the embedding on the first stage, the head on the last; the
depth is the profile's length less those two entries) and
the cell's micro-batch size B/Q. Divided by the median step of the traced
window, on the host's clock. 1 is a perfect prediction.
"""

import statistics


def read(rec):
    from repro.core import latency
    from repro.core.network import tpu_stage_network
    from repro.core.latency import SplitSolution

    if not rec.steps_ms:
        return None
    n = rec.stages
    layers = len(rec.planner_profile.fp_work) - 2
    per = layers // n
    cuts = [1 + per * (k + 1) for k in range(n)]
    cuts[-1] = layers + 2                       # the head on the last stage
    sol = SplitSolution(cuts=tuple(cuts), placement=tuple(range(n)))
    net = tpu_stage_network(n, rec.chips // n)
    b = rec.batch // rec.q
    pred = latency.total_latency(rec.planner_profile, net, sol, b, rec.batch)
    return pred / (statistics.median(rec.steps_ms) / 1e3)
