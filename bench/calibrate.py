"""Readings that the limits of a cell's correctness check are set from.

    python bench/calibrate.py --workload <cell> --seeds 12 --controls 3

In one process, for each seed: the program driven through the check steps
as a run drives it, against the plain float32 reference (the lower
readings). On the first ``--controls`` seeds also (``--faults`` picks
which):

- ``control``: the reference computed with float8 (e4m3) matmul operands in
  the program's place, the precision below the configuration's bfloat16;
- ``half_batch``: the reference on the first half of each batch's rows in
  the program's place (half of the batch left out, the mean over the rest);
- ``no_exchange`` (a cell whose step spans chips): the program with its
  ``lax.ppermute`` hand-offs replaced by the identity.

A state left unchanged reads 1 on ``grad_gap`` and ``change_gap`` by their
definition and needs no run. The benchmark's own runs never run this. It
prints one JSON line per reading and, last, the summary: per number the
largest program reading and the smallest reading of each fault.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _identity_ppermute(x, axis_name, perm):
    return x


FAULTS = ("control", "half_batch", "no_exchange")


def calibrate(name: str, seeds: list, controls: int, *, faults=FAULTS,
              root=None, require_tpu: bool = True, out=print) -> dict:
    import jax

    from harness import check, runner, spec

    h = runner.Harness(name, root=root or spec.ROOT, require_tpu=require_tpu,
                       log=lambda s: print(s, file=sys.stderr, flush=True))
    readings = {"program": [], "control": [], "half_batch": [],
                "no_exchange": []}
    faulty = None
    if h.cell.chips > 1 and "no_exchange" in faults:
        faulty = h.programs.build(h.cell, jax.eval_shape(
            lambda k: h.ref.init_params(k, h.c), jax.random.key(0)),
            h.devices)

    def record(kind, seed, prog_readings, ref_readings):
        g = check.gaps(prog_readings, ref_readings)
        readings[kind].append(g)
        out(json.dumps({"kind": kind, "seed": seed, **g,
                        "losses": prog_readings["losses"],
                        "ref_losses": ref_readings["losses"]}))

    for i, seed in enumerate(seeds):
        key = jax.random.key(seed)
        check_batches, _ = h.batches(seed)
        with h.prog.mesh_context():
            params, state, prog_r = h.drive_check(key, check_batches)
        del params, state
        ref_r = h.reference(key, check_batches)
        record("program", seed, prog_r, ref_r)
        if i >= controls:
            continue
        if "control" in faults:
            record("control", seed, h.reference(key, check_batches,
                                                quant="fp8"), ref_r)
        if "half_batch" in faults:
            record("half_batch", seed, h.reference(
                key, check_batches, rows=h.cell.batch // 2), ref_r)
        if faulty is not None:
            with faulty.mesh_context(), mock.patch.object(
                    jax.lax, "ppermute", _identity_ppermute):
                params, state, bad = h.drive_check(key, check_batches,
                                                   prog=faulty)
            del params, state
            record("no_exchange", seed, bad, ref_r)
    summary = {"cell": name, "seeds": seeds}
    for n in check.NAMES:
        summary[n] = {"lower": max(r[n] for r in readings["program"])}
        for kind in FAULTS:
            if readings[kind]:
                summary[n][kind] = min(r[n] for r in readings[kind])
        if n != "loss_gap":
            summary[n]["unchanged_state"] = 1.0
    out(json.dumps({"summary": summary}))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help="which of the faults to read, comma-separated")
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    with contextlib.suppress(BrokenPipeError):
        calibrate(args.workload, seeds, args.controls,
                  faults=tuple(args.faults.split(",")),
                  out=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
