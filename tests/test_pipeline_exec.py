"""Executor equivalences: micro-batched grads == full batch; the SL
executor trains (loss decreases) and charges the analytic latency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ours, vgg16_profile, make_edge_network
from repro.data import classification_batches, client_datasets
from repro.models import vgg as vgg_lib
from repro.pipeline import (LinkHooks, SplitLearningExecutor,
                            microbatch_grads, split_batch)


def test_microbatch_grads_equal_full_batch():
    """The paper's synchronous-SGD guarantee (Fig. 4: same convergence)."""
    rng = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(rng, (8, 4)),
              "b": jnp.zeros((4,))}

    def loss_fn(p, batch):
        logits = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((logits - batch["y"]) ** 2)

    batch = {"x": jax.random.normal(rng, (16, 8)),
             "y": jax.random.normal(rng, (16, 4))}
    l_full, g_full = jax.value_and_grad(loss_fn)(params, batch)
    for q in (1, 2, 4, 8, 16):
        l_mb, g_mb = microbatch_grads(loss_fn, params, batch, q)
        assert float(l_mb) == pytest.approx(float(l_full), rel=1e-6)
        for a, b in zip(jax.tree.leaves(g_mb), jax.tree.leaves(g_full)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


def test_split_batch_shapes():
    batch = {"x": jnp.zeros((12, 3)), "y": jnp.zeros((12,))}
    mb = split_batch(batch, 4)
    assert mb["x"].shape == (4, 3, 3)
    assert mb["y"].shape == (4, 3)


@pytest.fixture(scope="module")
def sl_setup():
    profile = vgg16_profile(work_units="bytes")
    net = make_edge_network(num_servers=4, num_clients=2, seed=3,
                            kappa=1 / 32.0)
    plan = ours(profile, net, B=16, b0=4)
    return profile, net, plan


def test_sl_executor_trains(sl_setup):
    profile, net, plan = sl_setup
    ex = SplitLearningExecutor(plan, profile, net, seed=0)
    batch = {k: jnp.asarray(v)
             for k, v in next(classification_batches(batch=16, seed=0)).items()}
    # overfit one batch: monotone-ish loss decrease is guaranteed
    # (lr retuned for the He-gain VGG init — 0.05 overshoots with
    # properly-scaled gradients)
    losses = [ex.train_round(batch, lr=0.01) for _ in range(3)]
    assert losses[-1] < losses[0]
    # the sim clock advances by the plan latency per round
    assert ex.simulated_time == pytest.approx(3 * plan.L_t)


def test_sl_executor_momentum_is_heavy_ball(sl_setup):
    """The executor's momentum buffer persists across rounds: three rounds
    match a plain heavy-ball loop on the unsplit model."""
    profile, net, plan = sl_setup
    ex = SplitLearningExecutor(plan, profile, net, seed=0)
    batch = {k: jnp.asarray(v)
             for k, v in next(classification_batches(batch=16, seed=0)).items()}
    params = ex.full_params
    vg = jax.jit(jax.value_and_grad(vgg_lib.loss_fn))
    vel, want = None, []
    for _ in range(3):
        loss, g = vg(params, batch)
        want.append(float(loss))
        vel = g if vel is None else jax.tree.map(
            lambda v, gg: 0.9 * v + gg, vel, g)
        params = jax.tree.map(lambda p, v: p - 0.01 * v, params, vel)
    got = [ex.train_round(batch, lr=0.01, momentum=0.9) for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_sl_executor_with_compression(sl_setup):
    from repro.compression import make_link_hooks
    profile, net, plan = sl_setup
    ex = SplitLearningExecutor(plan, profile, net, seed=0,
                               hooks=make_link_hooks("int8"))
    batch = {k: jnp.asarray(v)
             for k, v in next(classification_batches(batch=16, seed=1)).items()}
    losses = [ex.train_round(batch, lr=0.01) for _ in range(3)]
    assert losses[-1] < losses[0]          # int8 links don't break training


def test_vgg_stage_chain_equals_full_forward():
    from repro.pipeline import vgg_stages_from_cuts, split_vgg_params
    rng = jax.random.PRNGKey(1)
    params = vgg_lib.init_params(rng)
    x = jax.random.normal(rng, (2, 32, 32, 3))
    full = vgg_lib.forward(params, x)
    cuts = (3, 9, 16)
    stages = vgg_stages_from_cuts(cuts)
    parts = split_vgg_params(params, cuts)
    y = x
    for st, sp in zip(stages, parts):
        y = st.forward(sp, y)
    np.testing.assert_allclose(np.asarray(full), np.asarray(y), atol=1e-5)


def test_client_datasets_partitions():
    ds = client_datasets(4, samples=512, iid=False, alpha=0.3, seed=0)
    assert len(ds) == 4
    total = sum(len(d.labels) for d in ds)
    assert total == 512
    draw = ds[0].draw(8)
    assert draw["images"].shape == (8, 32, 32, 3)
