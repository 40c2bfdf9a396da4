"""model.attention_ms: device time per step in attention's score, softmax and value product.

The union of each device's operations under the program's
``model.attention`` scope (forward, its recomputation under remat, and
backward; not the QKV and output projections), per traced step, the mean
over the chips used. None where the trace holds no op under that scope.
"""

from harness import scopes

SCOPE = "model.attention"


def read(rec):
    return scopes.ms_per_step(rec, SCOPE)
