"""Pallas min-plus / min-max scan kernel for the Algorithm-1 layered DP.

``sweep_minplus`` runs the full K-layer masked relaxation for a batch of
thresholds in one ``pl.pallas_call`` (grid over threshold tiles), mirroring
the numpy reference in :mod:`repro.core.shortest_path` (``_sweep``).  It
compiles for the TPU; CPU callers pass ``interpret=True`` (correct but
slow, kept for parity tests) — the XLA-fused jit backend in
:mod:`repro.core.planner_jax` is the fast CPU path.
"""

from .kernel import sweep_call, sweep_minplus
from .ref import sweep_ref

__all__ = ["sweep_call", "sweep_minplus", "sweep_ref"]
