"""Pipeline parallelism: GPipe-style microbatch schedule over the "pp" axis.

The reference has no explicit pipeline scheduler — its async engine
dataflow-pipelines model-parallel graphs implicitly (SURVEY §2.4 row
'Pipeline parallelism'). TPU-natively the schedule must be explicit and
static: each mesh "pp" device holds one stage's parameters; activations hop
stage→stage via ``ppermute`` over ICI; the (num_micro + num_stages - 1)-step
loop is a ``lax.fori_loop`` so XLA overlaps the hop with the next
microbatch's compute.

Constraints (standard for this formulation): every stage maps activations
of one shape to the same shape (transformer-block-like), and
num_microbatches ≥ 1.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "pipeline_train_step", "make_pipeline_trainer",
           "PipelineTrainer"]


def _pp_body(params, xs, stage_fn, axis_name):
    """Per-device body. params: this stage's params (leading pp axis already
    split away by shard_map). xs: (n_micro, ...) microbatches — only stage
    0 reads them; outputs: (n_micro, ...) — only the last stage's are real."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: p[0], params)   # drop stacked pp dim
    n_micro = xs.shape[0]
    T = n_micro + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    mb_shape = xs.shape[1:]
    received = jnp.zeros(mb_shape, xs.dtype)
    outputs = jnp.zeros_like(xs)

    def step(t, carry):
        received, outputs = carry
        inject = lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
        act_in = jnp.where(idx == 0, inject, received)
        act_out = stage_fn(params, act_in)
        # last stage records its result for microbatch t-(n-1)
        out_slot = jnp.clip(t - (n - 1), 0, n_micro - 1)
        record = (idx == n - 1) & (t >= n - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(record,
                      act_out,
                      lax.dynamic_index_in_dim(outputs, out_slot, 0,
                                               keepdims=False)),
            out_slot, axis=0)
        received = lax.ppermute(act_out, axis_name, perm)
        return received, outputs

    _, outputs = lax.fori_loop(0, T, step, (received, outputs))
    # broadcast last stage's outputs to every device (so out_specs can be
    # replicated over pp)
    outputs = lax.psum(jnp.where(idx == n - 1, outputs, 0.0), axis_name)
    return outputs


def pipeline_apply(stage_fn, stacked_params, x, mesh, axis="pp",
                   num_microbatches=None):
    """Run x through num_stages stages, stage i using stacked_params[...][i].

    stacked_params: pytree whose leaves have a leading axis of size
    mesh.shape[axis] (one slice per stage). x: (batch, ...) global input.
    Returns (batch, ...) output of the final stage.
    """
    n_stages = mesh.shape[axis]
    if num_microbatches is None:
        num_microbatches = n_stages
    B = x.shape[0]
    assert B % num_microbatches == 0, \
        "batch %d not divisible into %d microbatches" % (B, num_microbatches)
    mb = B // num_microbatches
    xs = x.reshape((num_microbatches, mb) + x.shape[1:])

    p_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = shard_map(
        functools.partial(_pp_body, stage_fn=stage_fn, axis_name=axis),
        mesh=mesh,
        in_specs=(p_spec, P()),
        out_specs=P(),
        check_vma=False)
    out = fn(stacked_params, xs)
    return out.reshape((B,) + out.shape[2:])


def pipeline_train_step(stage_fn, stacked_params, x, y, loss_fn, mesh,
                        axis="pp", num_microbatches=None):
    """One pipeline *training* step: microbatched forward through the
    stages, loss on the last stage's output, backward re-traversing the
    schedule in reverse (the transpose of each ``ppermute`` hop is the
    opposite hop, so gradient activations ride the ring backwards), with
    gradient accumulation across microbatches falling out of the loop
    transpose.  Returns ``(loss, grads)`` with ``grads`` shaped like
    ``stacked_params`` (leading stage axis).

    The reference has no pipeline scheduler to mirror (SURVEY §2.4); this
    is the capability mandated by SURVEY §7 phase 11.
    """

    def objective(params):
        out = pipeline_apply(stage_fn, params, x, mesh, axis=axis,
                             num_microbatches=num_microbatches)
        return jnp.mean(loss_fn(out, y))

    return jax.value_and_grad(objective)(stacked_params)


def make_pipeline_trainer(stage_fn, loss_fn, mesh, axis="pp",
                          num_microbatches=None, learning_rate=0.01):
    """Jitted GPipe SGD trainer: returns ``train(params, x, y) ->
    (params, loss)`` with stage-sharded donated params."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def train(params, x, y):
        loss, grads = pipeline_train_step(stage_fn, params, x, y, loss_fn,
                                          mesh, axis=axis,
                                          num_microbatches=num_microbatches)
        params = jax.tree.map(lambda p, g: p - learning_rate * g,
                              params, grads)
        return params, loss

    return train


def _run_block(block, vals_by_name, x, train=True):
    """Functionalize one Gluon block: run it on a jax array with parameter
    values substituted (the DataParallelTrainer tracing pattern)."""
    from ..ndarray import NDArray
    from .. import autograd
    shadows = {n: NDArray(v) for n, v in vals_by_name.items()}
    with autograd._scope(recording=False, training=train):
        with block._trace_params(shadows):
            out = block.hybrid_forward_dispatch(NDArray(x))
    return out._read()


class PipelineTrainer(object):
    """GPipe training for a Gluon ``HybridSequential`` of identical stages.

    The round-2 gap this closes: pipeline parallelism existed only as a
    raw ``stage(params, x)`` function (make_pipeline_trainer) a framework
    user could not reach from a Block.  Here the stages ARE Gluon blocks:

        body = nn.HybridSequential()
        for _ in range(n_stages):
            body.add(TransformerBlock(...))        # identical structure
        trainer = PipelineTrainer(body, loss, mesh, pre=embed, post=head)
        loss = trainer.step(x, y)

    Each mesh "pp" device holds ONE stage's parameters (leaves stacked on
    a leading stage axis, sharded over the pipeline axis); activations hop
    stage-to-stage via ppermute; backward re-traverses the schedule in
    reverse (pipeline_train_step).  ``pre``/``post`` blocks (embedding /
    head — usually structurally different from the body stages) run
    replicated outside the ring.

    Constraints (the standard static-schedule formulation): body stages
    must be structurally identical (same param shapes, activation shape
    preserved); stochastic layers (Dropout) are not supported inside the
    scheduled body; BatchNorm aux-state updates inside the body are
    dropped.  Optimizer: SGD (reference Module-style lr).
    """

    def __init__(self, net, loss, mesh=None, axis="pp", num_microbatches=None,
                 learning_rate=0.01, pre=None, post=None):
        from .mesh import current_mesh
        self.net = net
        self.loss = loss
        self.mesh = mesh if mesh is not None else current_mesh(required=True)
        self.axis = axis
        self.num_microbatches = num_microbatches
        self.learning_rate = learning_rate
        self.pre = pre
        self.post = post
        self._stages = list(net._children)
        n = self.mesh.shape[axis]
        if len(self._stages) != n:
            raise ValueError(
                "net has %d stage blocks but mesh axis %r has %d devices"
                % (len(self._stages), axis, n))
        self._state = None
        self._jit = None

    # -- parameter plumbing ------------------------------------------------
    def _gather(self, example_x):
        from jax.sharding import NamedSharding
        from ..ndarray import NDArray
        x = example_x
        if self.pre is not None:
            x = self.pre(x)
        for blk in self._stages:
            x = blk(x)          # resolves deferred shapes stage by stage
        if self.post is not None:
            self.post(x)
        stage_vals = []
        template = self._stages[0]
        for blk in self._stages:
            vals = [p.data()._read() for p in blk.collect_params().values()]
            if type(blk) is not type(template):
                raise ValueError(
                    "pipeline stages must be the same block type: %s vs %s"
                    % (type(template).__name__, type(blk).__name__))
            if stage_vals and [v.shape for v in vals] != \
                    [v.shape for v in stage_vals[0]]:
                raise ValueError(
                    "pipeline stages are not structurally identical: %s vs "
                    "%s" % ([v.shape for v in stage_vals[0]],
                            [v.shape for v in vals]))
            stage_vals.append(vals)
        # the schedule executes EVERY stage through stage 0's forward
        # function — same shapes is not enough (Dense(tanh) vs Dense(relu)
        # would silently compute the wrong model).  Probe: each stage's
        # own forward must equal the template driven by its params.
        probe = example_x
        if self.pre is not None:
            probe = self.pre(probe)
        pv = probe._read()
        names = list(template.collect_params().keys())
        for blk, vals in zip(self._stages[1:], stage_vals[1:]):
            # both sides run through _run_block (same train mode), else a
            # training-sensitive layer (BatchNorm) would falsely differ
            own_names = list(blk.collect_params().keys())
            own = np.asarray(
                _run_block(blk, dict(zip(own_names, vals)), pv))
            via_tmpl = np.asarray(
                _run_block(template, dict(zip(names, vals)), pv))
            if not np.allclose(own, via_tmpl, rtol=1e-5, atol=1e-6):
                raise ValueError(
                    "pipeline stage %r computes differently from stage 0 "
                    "despite identical param shapes (e.g. a different "
                    "activation/config) — the GPipe schedule requires "
                    "functionally identical stages" % (blk.name,))
        stacked = [jnp.stack([sv[j] for sv in stage_vals])
                   for j in range(len(stage_vals[0]))]
        stage_sh = NamedSharding(self.mesh, P(self.axis))
        repl = NamedSharding(self.mesh, P())
        self._stage_names = [list(b.collect_params().keys())
                             for b in self._stages]
        self._template_names = list(
            self._stages[0].collect_params().keys())
        state = {
            "stages": [jax.device_put(s, stage_sh) for s in stacked],
            "pre": {n: jax.device_put(p.data()._read(), repl)
                    for n, p in (self.pre.collect_params().items()
                                 if self.pre is not None else [])},
            "post": {n: jax.device_put(p.data()._read(), repl)
                     for n, p in (self.post.collect_params().items()
                                  if self.post is not None else [])},
        }
        self._state = state

    def _stage_fn(self):
        template = self._stages[0]
        names = self._template_names

        def fn(leaves, act):
            vals = dict(zip(names, leaves))
            return _run_block(template, vals, act)
        return fn

    def _build_jit(self):
        from jax.sharding import NamedSharding
        mesh, axis = self.mesh, self.axis
        stage_sh = NamedSharding(mesh, P(axis))
        repl = NamedSharding(mesh, P())
        pre_blk, post_blk, loss_blk = self.pre, self.post, self.loss
        stage_fn = self._stage_fn()
        n_micro = self.num_microbatches

        def objective(state, x, y):
            from ..ndarray import NDArray
            if pre_blk is not None:
                x = _run_block(pre_blk, state["pre"], x)
            out = pipeline_apply(stage_fn, state["stages"], x, mesh,
                                 axis=axis, num_microbatches=n_micro)
            if post_blk is not None:
                out = _run_block(post_blk, state["post"], out)
            per = loss_blk(NDArray(out), NDArray(y))
            return jnp.mean(per._read())

        # lr rides as a traced OPERAND (GL305): baking self.learning_rate
        # here would silently pin the schedule to its _build_jit-time
        # value (DataParallelTrainer's step takes its lr the same way)
        def step(state, x, y, lr):
            loss, grads = jax.value_and_grad(objective)(state, x, y)
            new_state = jax.tree.map(lambda p, g: p - lr * g, state, grads)
            return new_state, loss

        shardings = {"stages": [stage_sh] * len(self._state["stages"]),
                     "pre": {n: repl for n in self._state["pre"]},
                     "post": {n: repl for n in self._state["post"]}}
        self._jit = jax.jit(step,
                            in_shardings=(shardings, repl, repl, repl),
                            out_shardings=(shardings, repl),
                            donate_argnums=(0,))

    # -- public surface ----------------------------------------------------
    def step(self, data, label):
        """One pipeline-parallel training step; returns the device loss."""
        from ..ndarray import NDArray
        from .mesh import use_mesh
        x = data._read() if isinstance(data, NDArray) else jnp.asarray(data)
        y = label._read() if isinstance(label, NDArray) else jnp.asarray(label)
        if self._state is None:
            self._gather(NDArray(x))
            self._build_jit()
        with use_mesh(self.mesh):
            self._state, loss = self._jit(
                self._state, x, y,
                jnp.asarray(self.learning_rate, jnp.float32))
        return loss

    def sync_params(self):
        """Write trained values back into the Gluon blocks."""
        from ..ndarray import NDArray
        for j, name0 in enumerate(self._template_names):
            stacked = jax.device_get(self._state["stages"][j])
            for i, blk in enumerate(self._stages):
                pname = self._stage_names[i][j]
                blk.collect_params()[pname].data()._write(
                    jnp.asarray(stacked[i]))
        for blk, key in ((self.pre, "pre"), (self.post, "post")):
            if blk is None:
                continue
            for n, p in blk.collect_params().items():
                p.data()._write(jnp.asarray(
                    jax.device_get(self._state[key][n])))
