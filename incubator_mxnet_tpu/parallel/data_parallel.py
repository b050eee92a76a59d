"""DataParallelTrainer: the fused, sharded training step.

TPU-native replacement for Module's DataParallelExecutorGroup + KVStore
update loop (ref: python/mxnet/module/executor_group.py:129,267 +
gluon/trainer.py:156):

* the whole train step — forward, loss, backward, optimizer update — is ONE
  jitted XLA program (the reference needed engine bulking + fused optimizer
  ops to approximate this; XLA gives it outright),
* the batch is sharded over the mesh "dp" axis; parameters are replicated;
  XLA inserts the gradient all-reduce (psum over ICI) exactly where the
  reference ran Comm::Reduce / NCCL allreduce,
* parameters live on device between steps (donated buffers — no host
  round-trips); ``sync_params()`` writes them back into the Gluon Block.

Works on any mesh: 1 real TPU chip, a v5e slice, or the 8-device virtual
CPU mesh used by tests and the driver's multi-chip dry-run.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ndarray import NDArray
from .. import autograd, random_state
from ..ops.registry import get_op
from ..telemetry import blackbox as _blackbox, xray as _xray
from ..telemetry.tracing import (collect_step_counters, phase_span,
                                 stack_step_counters)
from .mesh import data_parallel_mesh

__all__ = ["DataParallelTrainer", "pure_optimizer"]


def _spans_processes(sharding):
    """True when the sharding places shards on devices of other processes
    (multi-host mesh) — plain device_put can't reach those."""
    me = jax.process_index()
    return any(d.process_index != me for d in sharding.device_set)


def _global_put(value, sharding):
    """device_put that also works on process-spanning meshes: every process
    builds only its addressable shards from the host value (which multihost
    callers must hold replicated — see _gather_params' broadcast).  This is
    the placement role ps-lite's ZPull played; here it's a local slice-and-
    upload with zero cross-host traffic."""
    if not _spans_processes(sharding):
        return jax.device_put(value, sharding)
    v = np.asarray(value)
    return jax.make_array_from_callback(v.shape, sharding,
                                        lambda idx: v[idx])


def pure_optimizer(name, **hyper):
    """(init_state, update) pair built from the fused optimizer update ops
    (ops/optimizer_ops.py — the same kernels the eager Optimizer uses)."""
    name = name.lower()
    if name == "sgd":
        momentum = hyper.get("momentum", 0.0)
        if momentum:
            op = get_op("sgd_mom_update").fcompute

            def init(w):
                return (jnp.zeros_like(w),)

            def update(w, g, state, lr):
                new_w, new_mom = op(w, g, state[0], lr=lr,
                                    momentum=momentum,
                                    wd=hyper.get("wd", 0.0),
                                    rescale_grad=hyper.get("rescale_grad", 1.0),
                                    clip_gradient=hyper.get("clip_gradient", -1.0))
                return new_w, (new_mom,)
        else:
            op = get_op("sgd_update").fcompute

            def init(w):
                return ()

            def update(w, g, state, lr):
                return op(w, g, lr=lr, wd=hyper.get("wd", 0.0),
                          rescale_grad=hyper.get("rescale_grad", 1.0),
                          clip_gradient=hyper.get("clip_gradient", -1.0)), ()
        return init, update
    if name == "adam":
        op = get_op("adam_update").fcompute
        b1 = hyper.get("beta1", 0.9)
        b2 = hyper.get("beta2", 0.999)

        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w), jnp.zeros((), jnp.int32))

        def update(w, g, state, lr):
            mean, var, t = state
            t = t + 1
            tf = t.astype(jnp.float32)
            lr_t = lr * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
            new_w, new_mean, new_var = op(
                w, g, mean, var, lr=lr_t, beta1=b1, beta2=b2,
                epsilon=hyper.get("epsilon", 1e-8), wd=hyper.get("wd", 0.0),
                rescale_grad=hyper.get("rescale_grad", 1.0),
                clip_gradient=hyper.get("clip_gradient", -1.0))
            return new_w, (new_mean, new_var, t)
        return init, update
    raise ValueError("pure_optimizer: unsupported optimizer %r "
                     "(sgd and adam cover the fused-step path; others run "
                     "through the eager Trainer)" % name)


class DataParallelTrainer(object):
    """One-jit data-parallel trainer for a Gluon HybridBlock."""

    def __init__(self, block, loss, optimizer="sgd", optimizer_params=None,
                 mesh=None, donate=True, dtype=None):
        """``dtype='bfloat16'`` enables mixed precision: parameters and the
        optimizer stay in f32 master copies; activations and weights are
        cast to bf16 *inside* the jitted step (XLA fuses the casts into the
        convs/matmuls, which then run native bf16 MXU passes); the loss is
        computed in f32.  Same semantics as the reference's mp_sgd
        multi-precision path (src/operator/optimizer_op.cc mp_* ops), but
        the master/compute split lives in the one fused program."""
        self.block = block
        self.loss = loss
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        optimizer_params = dict(optimizer_params or {})
        self._lr = optimizer_params.pop("learning_rate", 0.01)
        self._opt_init, self._opt_update = pure_optimizer(optimizer,
                                                          **optimizer_params)
        self._donate = donate
        self._compute_dtype = jnp.dtype(dtype) if dtype is not None else None
        self._rng_key = None       # device-resident, carried through the step
        self._lr_dev = None        # cached device copy of the learning rate
        self._params = None        # name -> jax array (device-resident)
        self._opt_state = None
        self._trainable = None
        self._jit_cache = {}
        self._steps = 0            # step id of the program spans
        self._noted = None         # the program the registry last got
        self._count_labels = {}    # labels of the step's counters, by trace

    # -- parameter plumbing ------------------------------------------------
    def _gather_params(self, example_x):
        """The Block's parameters and their optimizer state onto the mesh,
        under the set-up spans ``deferred_init`` (inside
        ``_run_deferred_init``, where shapes are still open) and
        ``gather_params`` (the rest)."""
        blk_params = self.block.collect_params()
        if any(p._data is None and p._deferred_init
               for p in blk_params.values()):
            # Resolve deferred shapes with one eager pass over ONE example
            # row: parameter shapes do not depend on the batch, and the
            # whole global batch would sit on the default device alone
            # (dp=4: four chips' worth of activations on chip 0).  The row
            # must be on the SAME device as the Block's params (default
            # backend) and at compute dtype — host-pinned uint8 pipeline
            # batches are neither, so it round-trips through numpy here
            row = jnp.asarray(np.asarray(example_x[:1]))
            if jnp.issubdtype(row.dtype, jnp.integer):
                row = row.astype(jnp.float32)
            self.block._run_deferred_init(NDArray(row))
        with phase_span("gather_params"):
            self._put_params(blk_params)

    def _put_params(self, blk_params):
        repl = NamedSharding(self.mesh, P())
        multihost = _spans_processes(repl)
        vals = {n: p.data()._read() for n, p in blk_params.items()}
        if multihost:
            # rank 0's initialization wins, exactly the reference's
            # KVStore::Init broadcast semantics (kvstore_dist.h — first
            # pushed value defines the key); ONE batched collective
            from jax.experimental import multihost_utils
            vals = {n: np.asarray(v)
                    for n, v in multihost_utils.broadcast_one_to_all(
                        {n: np.asarray(v) for n, v in vals.items()}).items()}
        self._params = {}
        self._param_sharding = {}
        self._trainable = []
        for name, p in blk_params.items():
            spec = P(*p.sharding) if getattr(p, "sharding", None) else P()
            sh = NamedSharding(self.mesh, spec)
            self._param_sharding[name] = sh
            self._params[name] = _global_put(vals[name], sh)
            if p.grad_req != "null":
                self._trainable.append(name)
        # optimizer state shards like its parameter (same layout, so the
        # fused update stays local — reference mp/rowsparse updates were
        # likewise colocated with the weight).  Single-host: init runs on
        # the already-sharded device array, so tp-sharded state is born
        # sharded (never materialized whole on one device); multihost:
        # init runs on the host value and shards go up via _global_put.
        self._opt_state = {}
        for n in self._trainable:
            sh = self._param_sharding[n]
            seed = jnp.asarray(vals[n]) if multihost else self._params[n]
            self._opt_state[n] = jax.tree.map(
                lambda x, sh=sh, n=n: _global_put(
                    x, sh if getattr(x, "ndim", 0) ==
                    len(self._params[n].shape) else repl),
                self._opt_init(seed))

    def sync_params(self):
        """Write device params back into the Block (checkpoint/export path).

        Mesh-sharded buffers are pulled to host first: Block params must be
        plain single-device arrays so eager eval/save work regardless of
        the trainer's mesh.
        """
        blk_params = self.block.collect_params()
        repl = NamedSharding(self.mesh, P())
        gather = None
        for name, v in self._params.items():
            if not v.sharding.is_fully_replicated:
                # tp/ep-sharded buffers: allgather to replicated first so
                # the host fetch sees a fully-addressable array even on
                # multi-host meshes
                if gather is None:
                    gather = jax.jit(lambda a: a, out_shardings=repl)
                v = gather(v)
            blk_params[name].data()._write(jnp.asarray(jax.device_get(v)))

    # -- the pure step -----------------------------------------------------
    def _make_step(self, train=True):
        block, loss_blk = self.block, self.loss
        trainable = list(self._trainable)
        opt_update = self._opt_update
        cdt = self._compute_dtype

        def forward_loss(trainable_vals, frozen_vals, x, y, rng):
            all_vals = dict(frozen_vals)
            if cdt is not None:
                # compute-dtype cast happens inside the differentiated fn so
                # grads arrive back in f32 (cast transpose = cast back).
                # Only *trainable* params are cast: frozen values include BN
                # running stats, which must never be re-quantized to bf16
                # (the momentum blend would drift them every step)
                all_vals.update({n: v.astype(cdt)
                                 if v.dtype == jnp.float32 else v
                                 for n, v in trainable_vals.items()})
                # f32 inputs AND narrow-integer images (uint8/int16 data
                # pipelines) cast on device, keeping host batches
                # cast-free.  int32/int64 inputs are index data (token
                # ids for Embedding) and must NOT be rounded through the
                # compute dtype — bf16 resolves only 256 values per
                # binade, so large vocab ids would land on multiples of
                # 64 (and the top id past the table).
                if x.dtype == jnp.float32 or x.dtype in (
                        jnp.uint8, jnp.int8, jnp.uint16, jnp.int16):
                    x = x.astype(cdt)
            else:
                all_vals.update(trainable_vals)
                if jnp.issubdtype(x.dtype, jnp.integer):
                    x = x.astype(jnp.float32)
            shadows = {n: NDArray(v) for n, v in all_vals.items()}
            ndx, ndy = NDArray(x), NDArray(y)
            with collect_step_counters() as found, \
                    random_state.use_key(rng):
                with autograd._scope(recording=False, training=train):
                    with block._trace_params(shadows):
                        out = block.hybrid_forward_dispatch(ndx)
                    if cdt is not None:
                        out = NDArray(out._read().astype(jnp.float32))
                    per_sample = loss_blk(out, ndy)
            aux = {n: s._read() for n, s in shadows.items() if s._version > 0}
            # what the layers counted (telemetry.step_counter) leaves beside
            # the aux states, outside the differentiated value; a model that
            # counts nothing adds no result to the program
            counts, self._count_labels = stack_step_counters(found)
            return jnp.mean(per_sample._read()), (aux, counts)

        def dp_train_step(params, opt_state, rng_key, x, y, lr):
            # rng key lives on device across steps: split here, return the
            # next key — no host RNG round trip per step
            next_key, rng = jax.random.split(rng_key)
            tvals = {n: params[n] for n in trainable}
            fvals = {n: v for n, v in params.items() if n not in tvals}
            # the three phases are staged under the scopes telemetry/xray.py
            # parses out of the optimized HLO (every op's op_name path then
            # starts with its phase): jax.vjp and not value_and_grad, which
            # gives the pullback no call site to put a scope around
            with jax.named_scope("xray:forward"):
                loss_val, pullback, (aux, counts) = jax.vjp(
                    lambda t: forward_loss(t, fvals, x, y, rng), tvals,
                    has_aux=True)
            with jax.named_scope("xray:backward"):
                grads, = pullback(jnp.ones_like(loss_val))
            new_params = dict(params)
            new_opt = {}
            with jax.named_scope("xray:update"):
                for n in trainable:
                    new_w, new_s = opt_update(params[n], grads[n],
                                              opt_state[n], lr)
                    new_params[n] = new_w.astype(params[n].dtype)
                    new_opt[n] = new_s
                for n, v in aux.items():
                    if n not in tvals:
                        new_params[n] = v.astype(new_params[n].dtype)
            return new_params, new_opt, next_key, loss_val, counts

        return dp_train_step

    def _sharding_trees(self):
        """(param tree, opt-state tree) of NamedShardings — honors
        per-parameter sharding annotations (tp/ep model parallelism)."""
        ptree = dict(self._param_sharding)
        otree = jax.tree.map(lambda x: x.sharding, self._opt_state)
        return ptree, otree

    def compile(self, *example_args):
        """Build + jit the step for the example shapes; returns the jitted fn."""
        if self._params is None:
            self._gather_params(example_args[0])
        key = tuple((tuple(a.shape), str(a.dtype)) for a in example_args)
        if key not in self._jit_cache:
            repl = NamedSharding(self.mesh, P())
            batch = NamedSharding(self.mesh, P("dp"))
            ptree, otree = self._sharding_trees()
            step = self._make_step(train=True)
            self._jit_cache[key] = jax.jit(
                step,
                in_shardings=(ptree, otree, repl, batch, batch, repl),
                out_shardings=(ptree, otree, repl, repl, repl),
                donate_argnums=(0, 1, 2) if self._donate else ())
        return self._jit_cache[key]

    def compile_multi(self, xs, ys):
        """Jit K chained steps as ONE XLA program: lax.scan over the step
        with the (K, batch, ...) data resident on device.  Amortizes
        per-launch dispatch/RPC overhead K× — the jit-level analogue of
        the reference engine's op bulking (threaded_engine.h BulkAppend),
        one level up: whole train steps are the ops being bulked."""
        key = ("multi", tuple(xs.shape), str(xs.dtype), tuple(ys.shape))
        if key not in self._jit_cache:
            repl = NamedSharding(self.mesh, P())
            batch = NamedSharding(self.mesh, P(None, "dp"))
            ptree, otree = self._sharding_trees()
            step = self._make_step(train=True)

            def dp_train_multi_step(params, opt_state, rng_key, xs, ys, lr):
                def body(carry, xy):
                    p, s, k = carry
                    x, y = xy
                    p, s, k, loss, counts = step(p, s, k, x, y, lr)
                    return (p, s, k), (loss, counts)

                (params, opt_state, rng_key), (losses, counts) = \
                    jax.lax.scan(body, (params, opt_state, rng_key),
                                 (xs, ys))
                return params, opt_state, rng_key, losses[-1], counts

            self._jit_cache[key] = jax.jit(
                dp_train_multi_step,
                in_shardings=(ptree, otree, repl, batch, batch, repl),
                out_shardings=(ptree, otree, repl, repl, repl),
                donate_argnums=(0, 1, 2) if self._donate else ())
        return self._jit_cache[key]

    def _prepare_inputs(self, data, label, batch_spec, multi=False):
        """Shared dispatch prologue: resolve params (deferred init runs on
        one single-device example row, BEFORE mesh sharding), device-resident
        rng/lr, batch arrays laid out per ``batch_spec`` (resharding
        skipped when already placed)."""
        x = data._read() if isinstance(data, NDArray) else data
        y = label._read() if isinstance(label, NDArray) else label
        if self._params is None:
            rows = x if hasattr(x, "shape") else np.asarray(x)
            self._gather_params(rows[0] if multi else rows)
        repl = NamedSharding(self.mesh, P())
        batch_sh = NamedSharding(self.mesh, batch_spec)
        multihost = _spans_processes(repl)
        if self._rng_key is None:
            key = random_state.next_key()
            if multihost:
                # one shared dropout/shuffle stream across hosts (ranks
                # must trace identical programs with identical constants)
                from jax.experimental import multihost_utils
                key = multihost_utils.broadcast_one_to_all(key)
            self._rng_key = _global_put(key, repl)
        if self._lr_dev is None:
            self._lr_dev = _global_put(jnp.asarray(self._lr, jnp.float32),
                                       repl)
        def _place(v):
            if not hasattr(v, "sharding"):
                v = np.asarray(v)  # lists / scalars → one host array
            elif v.sharding.is_equivalent_to(batch_sh, v.ndim):
                return v
            if multihost:
                # each process contributes its LOCAL batch shard; jax glues
                # them into the global (world_batch, ...) array — the data-
                # parallel split the reference expressed as per-worker
                # slices of provide_data (executor_group.py DataParallel).
                # The input stays host-side numpy until this single upload
                # (no device bounce on the hot path).
                return jax.make_array_from_process_local_data(batch_sh,
                                                              np.asarray(v))
            return jax.device_put(v, batch_sh)

        return _place(x), _place(y)

    def _dispatch(self, name, compile_fn, data, label, batch_spec, multi):
        """One launch of a jitted step, under the program spans ``step``
        (which carries the step id) > ``place``, ``dispatch``; the program
        is handed to the registry (``telemetry.programs()``) the first
        time it runs, and what its layers counted to the flight recorder
        under the step's id, unread (``telemetry.step_counters()``)."""
        from .mesh import use_mesh
        self._steps += 1
        with use_mesh(self.mesh), phase_span("step", step=self._steps):
            # scope covers deferred-init (in _prepare_inputs) AND the
            # trace: mesh-aware layers resolve this mesh throughout
            with phase_span("place"):
                x, y = self._prepare_inputs(data, label, batch_spec,
                                            multi=multi)
            fn = compile_fn(x, y)
            args = (self._params, self._opt_state, self._rng_key, x, y,
                    self._lr_dev)
            if fn is not self._noted:
                _xray.register_program(name, fn, args)
                self._noted = fn
            with phase_span("dispatch"):
                (self._params, self._opt_state, self._rng_key, loss_val,
                 counts) = fn(*args)
        if counts:
            _blackbox.step_counts(self._steps, counts, self._count_labels)
        return loss_val

    def step_multi(self, datas, labels):
        """Run K chained steps in one launch; ``datas`` (K, batch, ...),
        ``labels`` (K, batch).  Returns the last step's device loss."""
        return self._dispatch("dp_train_multi_step", self.compile_multi,
                              datas, labels, P(None, "dp"), True)

    def step(self, data, label):
        """Run one sharded train step; returns the device scalar loss.

        The trainer's mesh is scoped for the trace (parallel.use_mesh), so
        mesh-aware layers (MultiHeadAttention(seq_axis=...), capacity MoE)
        resolve THIS mesh without the caller wrapping every step."""
        return self._dispatch("dp_train_step", self.compile, data, label,
                              P("dp"), False)

    def compiled_step(self, data, label):
        """The compiled program of ``step`` for this batch, a
        ``jax.stages.Compiled``: its optimized HLO (``as_text()``), its
        ``memory_analysis()`` and ``cost_analysis()``.  Lowered from the
        shapes and shardings of the trainer's state, so nothing runs and
        nothing is donated; a step that has already run is read back from
        the compile cache."""
        from .mesh import use_mesh
        with use_mesh(self.mesh):
            x, y = self._prepare_inputs(data, label, P("dp"))
            with phase_span("memory_analysis"):
                return self.compile(x, y).lower(*_xray.abstract((
                    self._params, self._opt_state, self._rng_key, x, y,
                    self._lr_dev))).compile()

    @property
    def learning_rate(self):
        return self._lr

    def set_learning_rate(self, lr):
        self._lr = lr
        self._lr_dev = None  # re-upload on next step
