"""End-to-end behaviour tests for the paper's system.

Full-loop integration: plan (BCD) -> pipelined SL training on synthetic
CIFAR-shaped data -> loss decreases, and the headline paper claims hold on
the analytical side (pipelined < no-pipeline; BCD near-optimal)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (evaluate_under_fluctuation, make_edge_network,
                        no_pipeline, optimal, ours, vgg16_profile)
from repro.data import classification_batches
from repro.launch.serve import BatchedServer, Request
from repro.launch.train import train
from repro.pipeline import SplitLearningExecutor


@pytest.fixture(scope="module")
def paper_setup():
    prof = vgg16_profile(work_units="bytes")
    net = make_edge_network(num_servers=6, num_clients=4, seed=1,
                            kappa=1 / 32.0)
    return prof, net


def test_paper_headline_pipelining_speedup(paper_setup):
    """Fig. 1(b)/Fig. 4: pipelined SL reaches any accuracy level several
    times faster than no-pipeline (identical per-round updates; only the
    per-round latency differs)."""
    prof, net = paper_setup
    p = ours(prof, net, B=512, b0=20)
    np_plan = no_pipeline(prof, net, B=512)
    speedup = np_plan.L_t / p.L_t
    assert speedup > 1.5
    print(f"pipelining speedup: {speedup:.2f}x")


def test_bcd_vs_optimal_gap_small(paper_setup):
    """Fig. 7(a): suboptimal BCD within a few percent of exhaustive."""
    prof, net = paper_setup
    p = ours(prof, net, B=128, b0=20)
    o = optimal(prof, net, B=128, b_step=1)
    assert p.L_t <= o.L_t * 1.05 + 1e-9, (p.L_t, o.L_t)


def test_fluctuation_robustness(paper_setup):
    """Fig. 6: moderate CV noise degrades latency gracefully (< 2x at
    CV = 0.2)."""
    prof, net = paper_setup
    p = ours(prof, net, B=512, b0=20)
    rep = evaluate_under_fluctuation(prof, net, p, cv=0.2, draws=16)
    assert rep.degradation < 2.0
    rep0 = evaluate_under_fluctuation(prof, net, p, cv=0.01, draws=8)
    assert rep0.degradation == pytest.approx(1.0, abs=0.15)


def test_end_to_end_sl_training_converges(paper_setup):
    """Accuracy rises on the synthetic CIFAR-shaped task within a few
    rounds of pipelined SL execution.

    The former seed-debt flake: the VGG's 1/sqrt(fan_in) init decayed
    activations ~1/sqrt(2) per ReLU layer, so logits sat at ~1e-3 and the
    overfit plateaued at the majority class.  Fixed by the He gain in
    ``models/vgg.py``; the test uses heavy-ball momentum (tames plain SGD's
    bounce on the norm-free stack) and a best-of-trailing-rounds margin,
    and asserts the loss drop, the actual convergence signal, alongside
    accuracy.

    Step size: at lr=0.02 (effective 0.2 under momentum 0.9) the loss
    bounces back up after round 1 for most inits, so the outcome hung on
    the init draw (``jax_threefry_partitionable``, the default since jax
    0.5, draws seed 2 into a failing init).  At lr=0.01 the loss falls by
    > 0.3 for each of seeds 0-7.  The momentum buffer carried across
    rounds is checked in tests/test_pipeline_exec.py.
    """
    prof, net = paper_setup
    plan = ours(prof, net, B=16, b0=4)
    ex = SplitLearningExecutor(plan, prof, net, seed=2)
    batch = {k: jnp.asarray(v)
             for k, v in next(classification_batches(batch=16, seed=0)).items()}
    first_acc = ex.evaluate(batch)
    accs, losses = [], []
    for _ in range(6):                     # single-batch overfit
        losses.append(ex.train_round(batch, lr=0.01, momentum=0.9))
        accs.append(ex.evaluate(batch))
    assert losses[-1] < losses[0] - 0.2, losses
    assert max(accs[-3:]) > max(first_acc, 0.2), (first_acc, accs)


def test_lm_trainer_loss_decreases():
    losses = train("qwen3-0.6b", reduced=True, steps=16, batch=16, seq=32,
                   microbatches=4, lr=2e-3, log_every=100)
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_batched_server_serves():
    srv = BatchedServer("qwen3-0.6b", reduced=True, batch=2, cache_len=48)
    rng = np.random.default_rng(0)
    for rid in range(4):
        srv.submit(Request(rid, rng.integers(0, srv.cfg.vocab, 8,
                                             ).astype(np.int32), max_new=6))
    stats = srv.run()
    assert len(stats["completed"]) == 4
    assert all(len(r.generated) >= 6 for r in stats["completed"])
