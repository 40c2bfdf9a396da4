"""Which attention path a transformer block takes (``flash_applies``).

The Pallas flash kernels are taken on a TPU, for a full sequence, with no
window, at a lane-aligned head_dim, and where no auto mesh axis of size > 1
could shard the attention tensors; everywhere else the block keeps the XLA
paths (``full_attention`` / ``chunked_attention``).  Each condition is
checked on both sides; the TPU compile of the kernel path is in
``test_tpu_compile.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.configs import get_config
from repro.models import transformer as tf
from repro.models.common import flash_applies

A, M, E = AxisType.Auto, AxisType.Manual, AxisType.Explicit
PIPE, PROD = ("data", "stage", "model"), ("data", "model")


def _cfg(**kw):
    cfg = dataclasses.replace(get_config("qwen3-0.6b", reduced=True),
                              d_model=256, n_heads=2, n_kv=1, d_head=128)
    return dataclasses.replace(cfg, **kw)


@pytest.mark.parametrize("backend, mode, window, hd, mesh, expected", [
    ("tpu", "train", 0, 128, None, True),
    ("cpu", "train", 0, 128, None, False),
    ("gpu", "train", 0, 128, None, False),
    ("tpu", "prefill", 0, 128, None, True),
    ("tpu", "decode", 0, 128, None, False),
    ("tpu", "train", 64, 128, None, False),
    ("tpu", "train", 0, 64, None, False),
    ("tpu", "train", 0, 256, None, True),
    # the pipeline's mesh inside its shard_map: only "stage" has size > 1,
    # and it is manual
    ("tpu", "train", 0, 128, (PIPE, (1, 4, 1), (A, M, A)), True),
    ("tpu", "train", 0, 128, (PIPE, (1, 4, 1), (A, A, A)), False),
    ("tpu", "train", 0, 128, (PROD, (2, 1), (A, A)), False),
    ("tpu", "train", 0, 128, (PROD, (1, 2), (A, E)), False),
    ("tpu", "train", 0, 128, (PROD, (1, 1), (A, A)), True),
])
def test_flash_applies_on_each_side_of_every_condition(
        monkeypatch, backend, mode, window, hd, mesh, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = _cfg(sliding_window=window, d_head=hd)
    if mesh is None:
        assert flash_applies(cfg, mode) is expected
        return
    names, sizes, types = mesh
    with jax.sharding.use_abstract_mesh(
            AbstractMesh(sizes, names, axis_types=types)):
        assert flash_applies(cfg, mode) is expected


def test_block_fwd_on_cpu_lowers_to_no_custom_call():
    """Off the TPU the block's forward and gradient are the XLA paths, as
    before the kernels had a caller: no custom call in the lowered or the
    compiled program, at a head_dim the kernels would take."""
    cfg = _cfg()
    params = tf.init_layer_params(jax.random.key(0), cfg)
    x = jnp.ones((2, 128, cfg.d_model), jnp.bfloat16)

    def loss(p, x):
        y, _ = tf.block_fwd(p, x, cfg, positions=jnp.arange(128))
        return jnp.sum(y.astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x)
    assert "custom_call" not in lowered.as_text()
    assert "custom-call" not in lowered.compile().as_text()
