"""SPMD pipeline parallelism — the paper's pipelined SL on a TPU mesh.

``shard_map`` with a *manual* "stage" axis (data/model stay auto): stage k's
layer block lives on mesh slice stage=k; activations hop stage->stage+1 via
``lax.ppermute`` — the TPU-native counterpart of the paper's inter-server
activation transmissions (Eqs. 5/6), with the reverse (gradient) hops of
Eqs. (9)/(10) generated automatically by autodiff's ppermute transpose.

Schedule: interleaved (circular) over S v virtual stages of L/(S v) layers,
T = Q v + S - 1 ticks of one scan.  Stage k runs virtual stages k, k+S,
k+2S, ...; each micro-batch goes round the stage ring v times, the last
stage handing it back to stage 0.  v = L/S (one layer a tick) where the
hand-off returns in time for the next round (Q >= S), so the fill and drain
take S - 1 of Q L/S + S - 1 layer-times instead of S - 1 of Q + S - 1
stage-times; else v = 1, which is GPipe's fill/steady/drain over Q + S - 1
ticks, the timeline the paper's Eq. (14) models (T_f fill + (Q-1) * T_i
steady).  The stage plan (cuts) and micro-batch count Q come from
core.planner — i.e. Algorithm 1 + Theorem 1 drive the actual runtime
configuration.

The embedding runs *outside* the pipelined region, replicated on every
stage, so all pipeline stages are structurally identical transformer-layer
blocks.  The LM head and loss follow the region.  An untied head whose
vocabulary the stages divide is split over the "stage" axis by vocabulary
(Megatron's vocab-parallel cross-entropy): stage k holds columns
[k V/S, (k+1) V/S) of ``lm_head`` and their optimizer state, computes its
slice of the logits, and the stages combine only per-token statistics (row
max, sum of exponentials, gold logit) and, in the backward, the head's
input gradient.  A tied or non-dividing head is replicated and every stage
runs it whole.  Either way the loss is accumulated per micro-batch to keep
the vocab-sized logits transient.  Numerics are validated against the plain
(non-pipelined) loss in tests/test_spmd.py.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.models.common import ArchConfig, cross_entropy, rms_norm
from repro.models import transformer as tf_lib
from repro.obs.device import scope
from .stage import transformer_stage_fn


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    num_stages: int
    num_microbatches: int
    stage_axis: str = "stage"


def head_vocab_shards(head, num_stages: int) -> int:
    """The ways the LM head splits over the stage axis by vocabulary:
    ``num_stages`` for an untied (d, V) ``head`` whose V the stages divide,
    else 1 (``head`` None for tied embeddings, or V does not divide)."""
    if head is None or len(head.shape) != 2 or head.shape[1] % num_stages:
        return 1
    return num_stages


def stage_shardings(mesh, tree):
    """Shardings that put the layer stacks of ``tree`` (params, or optimizer
    state that mirrors them) one block per stage, split ``lm_head`` by
    vocabulary over the stages where ``head_vocab_shards`` says so, and
    replicate the rest — the layout the pipeline and head regions'
    in_specs expect."""
    from jax.sharding import NamedSharding

    stages = mesh.shape["stage"]

    def spec(path, leaf):
        keys = {getattr(k, "key", None) for k in path}
        if "layers" in keys:
            return NamedSharding(mesh, P("stage"))
        if "lm_head" in keys and head_vocab_shards(leaf, stages) > 1:
            return NamedSharding(mesh, P(None, "stage"))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(spec, tree)


def virtual_stages(num_layers: int, num_stages: int,
                   num_microbatches: int) -> int:
    """v, the rounds a micro-batch makes of the stage ring: L/S, one layer
    a tick, where the ring's hand-off comes back to stage 0 in time for the
    next round (Q >= S); else 1, plain GPipe."""
    if num_microbatches < num_stages:
        return 1
    return num_layers // num_stages


def _round_robin_moves(num_stages: int, rounds: int):
    """The static plan that moves the stages' contiguous layer blocks to
    their owners on the ring.

    Stage k holds, in ``rounds`` blocks b, the virtual stages j = k v + b;
    the interleaved schedule runs j on stage j % S in round j // S.  Block
    b of stage k moves by the shift s = (j - k) % S.  Moves are grouped by
    shift, one collective-permute per block each stage sends at that shift
    (shift 0 stays put), so no stage receives two blocks in one permute.

    Returns (moves, slots): ``moves`` is a list of (shift, send) with
    ``send[k]`` the block stage k sends in that move, or None; ``slots[d][r]``
    is the move whose block stage d runs in round r.
    """
    S, v = num_stages, rounds
    by_shift = [[[] for _ in range(S)] for _ in range(S)]
    for k in range(S):
        for b in range(v):
            by_shift[(k * v + b - k) % S][k].append(b)
    moves, slots = [], [[None] * v for _ in range(S)]
    for s, sent in enumerate(by_shift):
        for i in range(max(len(bs) for bs in sent)):
            send = [bs[i] if i < len(bs) else None for bs in sent]
            for k, b in enumerate(send):
                if b is not None:
                    slots[(k + s) % S][(k * v + b) // S] = len(moves)
            moves.append((s, send))
    return moves, slots


def _by_stage(table, sid):
    """``table[sid]`` of a per-stage table: a constant where every stage
    reads the same."""
    if all(row == table[0] for row in table):
        return jnp.asarray(table[0], jnp.int32)
    return jnp.asarray(table, jnp.int32)[sid]


def _make_pipe_region(cfg: ArchConfig, pcfg: PipelineConfig, mesh,
                      rounds: int):
    """The manual-stage shard_map region: (layers (L, ...) split by
    contiguous blocks over the stages, stream (Q, mb, S, d)) -> (Q, mb, S,
    d), the stream in f32 both ways (the output is the head's input, whose
    gradient the vocab-parallel head sums over the stages).

    The schedule is interleaved (circular): ``rounds`` = v virtual stages
    per stage, each of L/(S v) layers; stage k runs virtual stages k, k+S,
    k+2S, ...; the micro-batches go round the ring v times.  Tick t of T =
    Q v + S - 1 runs, on stage k, work item w = t - k (valid for 0 <= w <
    Q v): round w // Q of micro-batch w % Q.  Stage 0 takes the stream in
    round 0 and the ring's hand-off (the last stage's output, ``ppermute``
    i -> i+1 mod S) in later rounds, after it waited Q - S ticks in a
    buffer; the last stage's round v - 1, the last Q ticks, is the output.
    With v = 1 (``virtual_stages``: Q < S, or one layer a stage) this is
    GPipe's fill/steady/drain over Q + S - 1 ticks with no wrap, the
    timeline Eq. (14) models.

    Before the ticks each stage sends the blocks it does not run to their
    owners (``_round_robin_moves``), one collective-permute per block; the
    layers' gradient returns the same way, in f32.
    """
    stage_fn = transformer_stage_fn(cfg)
    S_axis = pcfg.num_stages
    Q = pcfg.num_microbatches
    v = rounds
    T = Q * v + S_axis - 1
    lag = Q - S_axis if v > 1 else 0
    ax = pcfg.stage_axis
    moves, slots = _round_robin_moves(S_axis, v)
    hops = [(i, (i + 1) % S_axis)
            for i in range(S_axis if v > 1 else S_axis - 1)]

    def pipe(layers, stream_f32):
        # The stream crosses the shard_map boundary in f32: its transpose
        # cotangent is a psum over the stage axis, and XLA:CPU aborts on a
        # bf16 all-reduce ("Invalid binary instruction opcode copy", still
        # so on jax 0.9.0).  The TPU all-reduces bf16 natively; there the
        # f32 stream costs 2x its bf16 bytes.
        sid = jax.lax.axis_index(ax)
        stream = stream_f32.astype(cfg.compute_dtype)
        mb_shape = stream.shape[1:]
        blocks = jax.tree.map(
            lambda p: p.reshape((v, p.shape[0] // v) + p.shape[1:]), layers)

        def move(shift, send):
            b = _by_stage([0 if x is None else x for x in send], sid)
            blk = jax.tree.map(
                lambda p: jax.lax.dynamic_index_in_dim(p, b, keepdims=False),
                blocks)
            if shift == 0:
                return blk
            pairs = [(k, (k + shift) % S_axis)
                     for k, x in enumerate(send) if x is not None]
            return jax.lax.ppermute(blk, ax, pairs)

        if v == 1:
            stack = blocks
        else:
            with scope("pipe.relayout"):
                got = [move(*m) for m in moves]
                stack = jax.tree.map(lambda *b: jnp.stack(b), *got)
        slot_of = _by_stage(slots, sid)                  # (v,): round -> block

        def tick(state, t):
            carry, fifo = state
            if lag:             # the hand-off waits lag ticks on stage 0
                back = jax.lax.dynamic_index_in_dim(fifo, t % lag,
                                                    keepdims=False)
                fifo = jax.lax.dynamic_update_index_in_dim(fifo, carry,
                                                           t % lag, 0)
            else:
                back = carry
            x0 = jax.lax.dynamic_index_in_dim(stream, jnp.minimum(t, Q - 1),
                                              0, keepdims=False)
            x = jnp.where(sid == 0, jnp.where(t < Q, x0, back), carry)
            r = jnp.clip((t - sid) // Q, 0, v - 1)
            y = stage_fn(stack, slot_of[r], x)
            shifted = jax.lax.ppermute(y, ax, hops)
            out_t = jnp.where(sid == S_axis - 1, y, jnp.zeros_like(y))
            return (shifted, fifo), out_t

        init = (jnp.zeros(mb_shape, stream.dtype),
                jnp.zeros((lag,) + mb_shape, stream.dtype))
        # one scan over all ticks: a scan per phase (fill, steady, drain)
        # compiles for a v5e with 1.8 GB more temporary memory per device
        # (qwen1.5-4b, 5-layer stages, Q=4), so the phases share one name
        with scope("pipe.ticks"):
            _, outs = jax.lax.scan(tick, init, jnp.arange(T))
        valid = outs[T - Q:]                           # (Q, mb, seq, d)
        # combine: only the last stage holds nonzero outputs.  psum in f32
        # for the same XLA:CPU abort as above.
        with scope("pipe.combine"):
            return jax.lax.psum(valid.astype(jnp.float32), ax)

    # manual over "stage" only; data/model stay auto so the stream keeps
    # its outer sharding through the region
    return jax.shard_map(
        pipe, mesh=mesh,
        in_specs=(P(ax), P()),        # layers split; stream replicated
        out_specs=P(),                # identical across stages after psum
        axis_names={ax}, check_vma=False)


def _make_head_region(cfg: ArchConfig, pcfg: PipelineConfig, mesh):
    """The vocabulary-parallel head and loss, manual over "stage":
    (final_norm, lm_head (d, V) split by column, stream (Q, mb, S, d) f32,
    labels (Q, mb, S)) -> the mean of the micro-batches' token-mean loss.

    Every cross-stage sum is in f32, forward and backward, for the XLA:CPU
    bf16 all-reduce abort that ``_make_pipe_region`` describes: the row
    max, the sum of exponentials, the gold logit, and (the transpose of the
    replicated in_specs) the input and final-norm gradients."""
    ax = pcfg.stage_axis
    Q = pcfg.num_microbatches

    def head_loss(final_norm, lm_head, stream_f32, labels):
        v = lm_head.shape[1]                       # this stage's V / S
        lo = jax.lax.axis_index(ax) * v
        w = lm_head.astype(cfg.compute_dtype)

        def body(acc, inp):
            y, lab = inp
            x = rms_norm(y.astype(cfg.compute_dtype), final_norm,
                         cfg.norm_eps)
            logits = x @ w                         # (mb, S, V / S)
            m = jax.lax.pmax(jax.lax.stop_gradient(
                jnp.max(logits, axis=-1).astype(jnp.float32)), ax)
            shifted = logits - m.astype(logits.dtype)[..., None]
            sumexp = jax.lax.psum(
                jnp.sum(jnp.exp(shifted), axis=-1, dtype=jnp.float32), ax)
            local = lab - lo
            mine = (local >= 0) & (local < v)
            pick = jnp.take_along_axis(
                shifted, jnp.clip(local, 0, v - 1)[..., None], axis=-1)
            gold = jax.lax.psum(
                jnp.where(mine, pick[..., 0].astype(jnp.float32), 0.0), ax)
            nll = jnp.log(sumexp) - gold
            mask = lab != -1
            mean = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
            return acc + mean, None

        tot, _ = jax.lax.scan(body, jnp.float32(0.0), (stream_f32, labels))
        return tot / Q

    return jax.shard_map(
        head_loss, mesh=mesh,
        in_specs=(P(), P(None, ax), P(), P()),
        out_specs=P(),                # identical across stages after the sums
        axis_names={ax}, check_vma=False)


def make_pipelined_loss(cfg: ArchConfig, mesh, pcfg: PipelineConfig
                        ) -> Callable:
    """Returns loss(params, batch) running layers through the stage pipeline.

    ``params`` is the ordinary transformer param tree (stacked layers);
    stage stacking/sharding happens inside, so checkpoints are layout-
    compatible with the non-pipelined trainer.  The head is split by
    vocabulary over the stages where ``head_vocab_shards`` says so (it
    sets the counter ``pipe.head_vocab_shards`` while tracing).
    """
    Q = pcfg.num_microbatches
    rounds = virtual_stages(cfg.num_layers, pcfg.num_stages, Q)
    pipe = _make_pipe_region(cfg, pcfg, mesh, rounds)
    head_region = _make_head_region(cfg, pcfg, mesh)

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        assert B % Q == 0, (B, Q)
        from repro.models.common import maybe_constrain
        with scope("model.embed"):
            x = params["embed"].astype(cfg.compute_dtype)[tokens]
        stream = x.reshape(Q, B // Q, S, cfg.d_model).astype(jnp.float32)
        # shard the stream over data (micro-batch rows) AND model (d) on the
        # auto axes — it is replicated across "stage" by construction, and
        # leaving d unsharded costs 4x stream memory (§Perf iteration 2)
        stream = maybe_constrain(
            stream, P(None, ("pod", "data"), None, "model"))
        obs.gauge("pipe.virtual_stages", rounds)
        ys = pipe(params["layers"], stream)
        labels_mb = labels.reshape(Q, B // Q, S)
        head = None if cfg.tie_embeddings else params.get("lm_head")
        shards = head_vocab_shards(head, pcfg.num_stages)
        obs.gauge("pipe.head_vocab_shards", shards)
        if shards > 1:
            with scope("model.head_loss"):
                return head_region(params["final_norm"], head, ys, labels_mb)

        def head_loss(acc, inp):
            y, lab = inp
            logits = tf_lib._unembed(params, y.astype(cfg.compute_dtype), cfg)
            return acc + cross_entropy(logits, lab), None

        with scope("model.head_loss"):
            tot, _ = jax.lax.scan(head_loss, jnp.float32(0.0),
                                  (ys, labels_mb))
        return tot / Q

    return loss_fn


def make_pipelined_train_step(cfg: ArchConfig, mesh, pcfg: PipelineConfig,
                              optimizer) -> Callable:
    loss_fn = make_pipelined_loss(cfg, mesh, pcfg)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with scope("step.optimizer"):
            params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, {"loss": loss}

    return train_step


def plan_to_pipeline_config(stage_plan, global_batch: int) -> PipelineConfig:
    """core.planner.StagePlan -> runtime pipeline config (Q from Thm 1's b)."""
    q = max(1, min(stage_plan.num_microbatches, global_batch))
    while global_batch % q:
        q -= 1
    return PipelineConfig(num_stages=stage_plan.num_stages,
                          num_microbatches=q)
