"""model.head_loss_ms: device time per step in the final norm, the unembedding and the loss.

The union of each device's operations under the program's
``model.head_loss`` scope (forward, recomputation and backward), per traced
step, the mean over the chips used. The stage pipeline computes the head on
every stage, so there each chip reads the whole head. None where the trace
holds no op under that scope.
"""

from harness import scopes

SCOPE = "model.head_loss"


def read(rec):
    return scopes.ms_per_step(rec, SCOPE)
