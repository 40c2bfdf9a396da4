"""Names of the training step's parts on the device.

The compiler numbers the operations it emits (``fusion.535``), so a device
trace says how long each operation ran but not which part of the step it
belongs to.  The program wraps each part in ``scope(name)``, a
``jax.named_scope``, and the name travels with every HLO instruction the
part lowers to, in its ``op_name`` metadata (``jit(train_step)/
transpose(jvp(pipe.ticks))/while/body/model.attention/dot_general``).  A
reader of the trace maps each operation back to the scopes in that path
with ``scopes_of``.

A named scope is compile-time metadata: it changes no generated code, so it
costs nothing at run time, traced or not.  JAX is imported only when a
scope is entered, so the planner can import this package without it.

A TPU trace's events name the HLO instruction and carry no metadata, so
the ``op_name`` comes from the program's HLO: ``op_names`` reads it for
every instruction of a compiled program's text (``compiled.as_text()``).

``SCOPES`` is the whole vocabulary:

- ``model.embed``, ``model.blocks``, ``model.attention`` (the score,
  softmax and value product, without the projections), ``model.head_loss``
  (final norm, unembedding and cross-entropy);
- ``step.accumulate`` (the sum of micro-batch gradients),
  ``step.optimizer``;
- ``pipe.ticks``: the stage pipeline's Q v + S - 1 ticks, one scan, fill and
  drain included; ``pipe.relayout``, the collective-permutes that bring each
  stage the layers it runs under the interleaved schedule (v > 1 only);
  ``pipe.combine``, the sum that brings the last stage's outputs to every
  stage;
- ``kernels.flash``: the flash-attention kernels' ``pallas_call``s alone
  (forward and both backward kernels), inside ``model.attention``.
"""

from __future__ import annotations

import re

SCOPES = (
    "model.embed",
    "model.blocks",
    "model.attention",
    "model.head_loss",
    "step.accumulate",
    "step.optimizer",
    "pipe.ticks",
    "pipe.relayout",
    "pipe.combine",
    "kernels.flash",
)

_KNOWN = frozenset(SCOPES)
# a path component is a name, possibly inside transformations:
# ``transpose(jvp(pipe.ticks))`` -> ``pipe.ticks``
_WRAPPED = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|to_apply|calls|"
                     r"branch_computations)=\{?((?:%[\w.\-]+(?:, )?)+)")


def scope(name: str):
    """``jax.named_scope(name)`` for a name in ``SCOPES``; any other name
    raises ``ValueError``."""
    if name not in _KNOWN:
        raise ValueError(f"unknown device scope {name!r}; the program's "
                         f"scopes are {SCOPES}")
    import jax

    return jax.named_scope(name)


def scopes_of(op_name: str) -> tuple:
    """The program scopes named in an HLO ``op_name`` path, outermost
    first, each once."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        name = m.group(1) if m else part
        if name in _KNOWN and name not in out:
            out.append(name)
    return tuple(out)


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of an HLO module's text.

    An instruction the compiler made without metadata (a loop's counter, a
    copy of its carry) takes the ``op_name`` of the instruction that calls
    its computation: the ``while`` of a scan stands for the loop's
    bookkeeping.  Callees precede their callers in the text, so the
    computations are read from the last (the entry) back.
    """
    computations = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and computations:
            own = _OP_NAME.search(line)
            called = [c.strip().lstrip("%") for group in _CALLED.findall(line)
                      for c in group.split(",") if c.strip()]
            computations[-1][1].append(
                (m.group(1), own.group(1) if own else None, called))
            continue
        m = _COMPUTATION.match(line)
        if m:
            computations.append((m.group(1), []))
    caller_op, out = {}, {}
    for name, instructions in reversed(computations):
        inherited = caller_op.get(name)
        for inst, own, called in instructions:
            op = own or inherited
            if op:
                out[inst] = op
            for c in called:
                if op and c not in caller_op:
                    caller_op[c] = op
    return out
