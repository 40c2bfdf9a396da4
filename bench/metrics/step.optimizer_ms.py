"""step.optimizer_ms: device time per step in the optimizer's update.

The union of each device's operations under the program's
``step.optimizer`` scope, per traced step, the mean over the chips used.
None where the trace holds no op under that scope.
"""

from harness import scopes

SCOPE = "step.optimizer"


def read(rec):
    return scopes.ms_per_step(rec, SCOPE)
