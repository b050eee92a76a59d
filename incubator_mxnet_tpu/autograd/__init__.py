"""Autograd: tape-based reverse-mode differentiation over eager ops.

TPU-native rebirth of src/imperative/imperative.cc (+ python/mxnet/autograd.py):

* ``record()/pause()/train_mode()/predict_mode()`` scopes == the reference's
  thread-local ``is_recording_/is_train_`` flags (imperative.cc:25-29).
* Each recorded eager op stores the ``jax.vjp`` closure of its own jitted
  fcompute — the tape IS the gradient graph, so there is no separate
  ``pass::Gradient`` construction step (imperative.cc:433): XLA already owns
  the per-op backward kernels.
* ``backward()`` walks the tape in reverse accumulating cotangents
  (RunGraph over the backward graph, imperative.cc:268).  A node that
  can leave cotangents out (a recorded CachedOp) is told which of its
  inputs the pass has a use for (:func:`_wanted`).
* ``grad()`` with ``create_graph=True`` re-records each vjp application,
  giving higher-order gradients (parity with autograd.py:270).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "set_recording", "set_training", "mark_variables",
           "backward", "grad", "get_symbol", "Function",
           "backward_pass_id", "register_hook_source",
           "unregister_hook_source"]

_state = threading.local()

# graftlap: consumers that installed _grad_ready_hook attrs register here
# so a hook-less process never pays the per-backward finalization prescan
# (an O(tape fan-in) getattr walk).  A WeakSet: a Trainer dropped without
# disarming vanishes from the set on GC, re-gating the scan by itself.
import weakref as _weakref
_hook_sources = _weakref.WeakSet()


def register_hook_source(source):
    """Declare that ``source`` has grad-ready hooks installed somewhere
    (gluon's _BucketScheduler).  Only the set's non-emptiness matters."""
    _hook_sources.add(source)


def unregister_hook_source(source):
    _hook_sources.discard(source)


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
        _state.tape = []
        _state.backward_passes = 0
    return _state


def backward_pass_id():
    """Monotonic id of the calling thread's latest backward pass.

    graftlap consumers (the Trainer's bucket scheduler) use it to tell
    gradients of the CURRENT pass from leftovers of an earlier one: a
    grad-ready hook firing under a new pass id means every in-flight
    reduce issued during the previous pass is stale and must be
    discarded before scheduling restarts."""
    return _st().backward_passes


def is_recording():
    return _st().recording


def head_seed(value):
    """THE backward seeding rule for a head with no explicit head_grad:
    ones of the head's shape/dtype (``d(sum)/d`` semantics, parity with
    the reference's ``backward()``): what the tape walk
    (:func:`_run_backward`) seeds a bare head with."""
    return jnp.ones_like(value)


def is_training():
    return _st().training


def set_recording(is_recording):  # noqa: A002 - parity signature
    s = _st()
    prev = s.recording
    s.recording = bool(is_recording)
    return prev


def set_training(train_mode):
    s = _st()
    prev = s.training
    s.training = bool(train_mode)
    return prev


@contextmanager
def _scope(recording=None, training=None):
    s = _st()
    prev_r, prev_t = s.recording, s.training
    if recording is not None:
        s.recording = recording
    if training is not None:
        s.training = training
    try:
        yield
    finally:
        s.recording, s.training = prev_r, prev_t


def record(train_mode=True):
    """ref: autograd.py:93 record scope."""
    return _scope(recording=True, training=train_mode)


def pause(train_mode=False):
    return _scope(recording=False, training=train_mode)


def train_mode():
    return _scope(training=True)


def predict_mode():
    return _scope(training=False)


class TapeNode:
    __slots__ = ("op", "inputs", "outputs", "vjp", "fn", "release",
                 "selective", "used")

    def __init__(self, op, inputs, outputs, vjp, fn=None, release=None,
                 selective=False):
        self.op = op
        self.inputs = inputs      # list[NDArray] (strong refs keep tape valid)
        self.outputs = outputs    # list[NDArray]
        self.vjp = vjp
        self.fn = fn              # pure fn of inputs (higher-order replay)
        # drops what vjp holds of the forward (a CachedOp's residuals):
        # called after the pass that is this node's last, i.e. one that
        # does not retain the graph; the outputs may outlive the node's use
        self.release = release
        # vjp takes, after the cotangents, one bool an input: whether the
        # pass has a use for that input's cotangent (a CachedOp's backward
        # program computes only those); an eager op's vjp takes no such set
        self.selective = selective
        self.used = False


def _record(op, inputs, outputs, vjp_fn, fn=None, release=None,
            selective=False):
    """Called by ndarray.invoke under recording (RecordOp, imperative.cc:182)."""
    s = _st()
    node = TapeNode(op, inputs, outputs, vjp_fn, fn, release, selective)
    for i, o in enumerate(outputs):
        o._tape_ref = (node, i)
    s.tape.append(node)


def mark_variables(variables, gradients, grad_reqs="write"):
    """ref: imperative.cc:112 MarkVariables — attach grad buffers."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = req


def _run_backward(heads, head_grads, retain_graph, train_mode, variables=None,
                  create_graph=False):
    """Reverse pass over the tape (RunGraph over the gradient graph,
    imperative.cc:268).

    Plain mode accumulates raw device values.  With ``create_graph`` the
    pass runs *as recorded eager ops*: each vjp application becomes a new
    tape node whose inputs are the primal inputs plus the incoming
    cotangents (so second derivatives see both dependencies), and
    cotangent accumulation goes through the recorded add op — the
    returned gradients are ordinary tape-connected NDArrays.

    graftlap: arrays carrying a ``_grad_ready_hook`` attribute have their
    gradient delivered *mid-walk*, the moment it is final — an input's
    gradient can only change while nodes listing it as an input are
    processed, so once the reverse walk passes the input's earliest tape
    position the accumulated cotangent is the finished gradient.  The
    hook fires right after delivery, which is what lets the Trainer's
    bucket scheduler issue a bucket's allreduce while backward is still
    producing earlier-layer gradients.  Hooks are suppressed whenever
    the pass is not a plain full backward (``create_graph``, an explicit
    ``variables`` list, or ``retain_graph`` — where a later pass may
    legally re-write the delivered grads): consumers fall back to their
    serial path.
    """
    # any bulk-deferred segment must land its tape node before the walk
    # (a recorded segment only becomes a node at flush)
    from .. import engine as _engine
    _engine.flush(cause="autograd")

    s = _st()
    s.backward_passes += 1
    tape = list(s.tape)
    from ..telemetry import metrics as _tmetrics
    _tmetrics.autograd_backward(len(tape))
    grads: dict[int, object] = {}

    from ..ndarray.ndarray import NDArray, invoke
    from ..ops.registry import get_op

    def _seed(h, hg):
        v = head_seed(h._read()) if hg is None else hg._read()
        return NDArray(v) if create_graph else v

    for i, h in enumerate(heads):
        grads[id(h)] = _seed(h, None if head_grads is None
                             else head_grads[i])

    def _zero_ct(o):
        z = jnp.zeros_like(o._read())
        return NDArray(z) if create_graph else z

    def _accum(key, g):
        if key not in grads:
            grads[key] = g
        elif create_graph:
            grads[key] = invoke(get_op("elemwise_add"), [grads[key], g], {})
        else:
            grads[key] = grads[key] + g

    # graftlap finalization schedule: for every hooked grad-receiving
    # input, the tape index of its EARLIEST appearance — once the reverse
    # walk passes that index the accumulated cotangent is final.  Built
    # only for the plain full-backward shape (see docstring); hooked
    # arrays are delivered early, everything else keeps the end-of-walk
    # delivery below, so semantics are unchanged for non-participants.
    fire_hooks = variables is None and not create_graph \
        and not retain_graph and bool(_hook_sources)
    final_at = {}               # tape index -> [NDArray, ...]
    if fire_hooks:
        seen = set()
        for k, node in enumerate(tape):
            for idx, inp in enumerate(node.inputs):
                if idx in node.op.nograd_inputs or id(inp) in seen:
                    continue
                if getattr(inp, "_grad_ready_hook", None) is not None \
                        and inp._grad is not None \
                        and inp._grad_req != "null":
                    seen.add(id(inp))
                    final_at.setdefault(k, []).append(inp)
                    # graftduplex tape-order feedback: the earliest tape
                    # position is where this input's gradient FINALIZES
                    # on the reverse walk (higher = earlier).  The
                    # Trainer's bucket packer sorts on it
                    # (GRAFT_BUCKET_ORDER=tape) so first-to-finalize
                    # params share the first buckets and their reduces
                    # hit the wire earliest.
                    inp._tape_pos = k

    # what a selective node is told (see _wanted): made at the first such
    # node, so a tape of eager ops alone pays nothing
    on_tape = listed = None

    for k in range(len(tape) - 1, -1, -1):
        node = tape[k]
        if any(id(o) in grads for o in node.outputs):
            if node.used and not retain_graph:
                raise RuntimeError(
                    "graph already backpropagated; use retain_graph=True "
                    "(parity: mxnet 'hit a node twice' check)")
            out_cts = tuple(grads.get(id(o)) if id(o) in grads
                            else _zero_ct(o) for o in node.outputs)
            if create_graph:
                in_cts = _recorded_vjp(node, out_cts)
            else:
                ct = out_cts[0] if len(out_cts) == 1 else out_cts
                if node.selective:
                    if on_tape is None:
                        on_tape = {id(n) for n in tape}
                        listed = {id(v) for v in variables or ()}
                    in_cts = node.vjp(ct, _wanted(node, on_tape, listed))
                else:
                    in_cts = node.vjp(ct)
            for idx, (inp, g) in enumerate(zip(node.inputs, in_cts)):
                if idx in node.op.nograd_inputs or g is None:
                    continue
                _accum(id(inp), g)
            if not retain_graph:
                node.used = True
                if node.release is not None:
                    node.release()
        for arr in final_at.pop(k, ()):
            # final for this pass: deliver now and tell the scheduler —
            # last-layer grads (high tape indices) fire first, giving the
            # reverse-topological bucket order that lets their reduces
            # overlap the rest of the walk
            if id(arr) in grads:
                _deliver(arr, grads, create_graph)
                _fire_ready_hook(arr)

    results = None
    if variables is not None:
        results = []
        for v in variables:
            g = grads.get(id(v))
            if g is None:
                g = _zero_ct(v)
            results.append(g)
    for k, node in enumerate(tape):
        for arr in node.inputs:
            if _deliver(arr, grads, create_graph) and variables is None \
                    and not create_graph:
                # graftduplex tape-order feedback, the hook-less twin of
                # the prescan stamp above: this forward-order sweep hits
                # each delivered input at its EARLIEST tape position, so
                # the very FIRST backward hands the Trainer's bucket
                # packer its ordering — the first bucket plan is already
                # tape-ordered and never rebuilds (a rebuild would
                # abandon the transition step's in-flight reduces)
                arr._tape_pos = k
    for h in heads:
        _deliver(h, grads, create_graph)
    if not retain_graph and not create_graph:
        s.tape = [n for n in s.tape if not n.used]
    return results


def _wanted(node, on_tape, listed):
    """One bool an input of ``node``: whether this pass has a use for the
    input's cotangent.  It has when the cotangent will be delivered (a grad
    buffer whose request is not ``"null"``, :func:`_deliver`'s own test),
    returned (the caller listed the input among ``grad``'s ``variables``)
    or handed on (the input is the output of a node still on the tape).
    Any other cotangent the pass would drop at its end."""
    return tuple(
        (inp._grad is not None and inp._grad_req != "null")
        or id(inp) in listed
        or (inp._tape_ref is not None and id(inp._tape_ref[0]) in on_tape)
        for inp in node.inputs)


def _fire_ready_hook(arr):
    """Invoke one array's grad-ready hook; a broken hook must never take
    the user's backward pass down with it (the scheduler side marks
    itself broken and the Trainer falls back to the serial reduce)."""
    hook = getattr(arr, "_grad_ready_hook", None)
    if hook is None:
        return
    try:
        hook(arr)
    except Exception:
        import logging
        logging.getLogger("graftlap").exception(
            "grad-ready hook raised; gradient delivery is unaffected "
            "but overlapped reduces fall back to the serial path")


def _deliver(arr, grads, as_ndarray=False):
    """Write one array's accumulated cotangent into its grad buffer.
    Returns True when a delivery actually happened (the caller's
    forward-order sweep stamps ``_tape_pos`` off it)."""
    if arr._grad is not None and arr._grad_req != "null" and id(arr) in grads:
        g = grads[id(arr)]
        if as_ndarray:
            g = g._read()
        if arr._grad_req == "add":
            arr._grad._write(arr._grad._read() + g)
        else:
            arr._grad._write(jnp.asarray(g, arr._grad._read().dtype))
        grads.pop(id(arr))
        return True
    return False


def _recorded_vjp(node, ct_nds):
    """Apply one node's backward as a *recorded* op (higher-order path).

    Builds g(primals..., cts...) = vjp(node.fn at primals)(cts) and runs it
    through the same record machinery as any eager op, so the produced
    input-cotangents carry tape edges to both the primal inputs and the
    incoming cotangents — exactly the dependency set the reference's
    backward-of-backward graph has (pass::Gradient applied twice).
    """
    from ..ndarray.ndarray import NDArray
    from ..ops.registry import Operator

    n_in = len(node.inputs)
    if node.fn is None:
        # no replayable function: first-order cotangents flow, but they
        # cannot be differentiated again — warn now, and raise only if
        # someone actually backprops through them (the tape-less NDArrays
        # below act as constants; _run_backward never revisits them)
        import warnings
        warnings.warn(
            "create_graph=True through %r: its backward is an opaque "
            "callback (autograd.Function), so gradients flowing through "
            "it are first-order only — a second backward treats them as "
            "constants. Use regular ops or mx.operator custom ops for "
            "true higher-order support." % node.op.name, stacklevel=3)
        raw = node.vjp(tuple(c._read() for c in ct_nds)
                       if len(ct_nds) > 1 else ct_nds[0]._read())
        return tuple(NDArray(g) if g is not None else None for g in raw)

    def gfun(*args):
        prim = args[:n_in]
        cts = args[n_in:]
        out, vjp_fn = jax.vjp(node.fn, *prim)
        ct = cts[0] if len(cts) == 1 else tuple(cts)
        res = vjp_fn(ct)
        # single-output nodes hand their vjp a bare leaf (tape convention)
        return res[0] if n_in == 1 else res

    all_inputs = list(node.inputs) + list(ct_nds)
    vals = [a._read() for a in all_inputs]
    out_vals, vjp2 = jax.vjp(gfun, *vals)
    if not isinstance(out_vals, tuple):
        out_vals = (out_vals,)
    outs = [NDArray(v) for v in out_vals]
    bop = Operator("_backward_" + node.op.name, gfun,
                   num_inputs=len(all_inputs), num_outputs=len(outs))
    _record(bop, all_inputs, outs, vjp2, fn=gfun)
    return outs


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """ref: autograd.py:243 / MXAutogradBackwardEx."""
    from ..telemetry import tracing as _ttracing
    with _ttracing.phase_span("bwd"):
        with _scope(training=train_mode):
            _run_backward(heads, head_grads, retain_graph, train_mode)


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """ref: autograd.py:270 — return grads of heads w.r.t. variables."""
    from ..ndarray.ndarray import NDArray

    if retain_graph is None:
        retain_graph = create_graph
    # create_graph must record its own vjp/accumulation ops even when the
    # caller sits outside a record() scope (the reference's higher-order
    # backward always builds the grad-of-grad graph)
    with _scope(training=train_mode,
                recording=True if create_graph else None):
        raw = _run_backward(heads, head_grads, retain_graph, train_mode,
                            variables=variables, create_graph=create_graph)
    if create_graph:
        # already tape-connected NDArrays (see _recorded_vjp)
        return list(raw)
    return [NDArray(g, ctx=v._ctx) for g, v in zip(raw, variables)]


def get_symbol(x):
    """Trace history of x into a Symbol (ref: autograd.py get_symbol)."""
    from ..symbol import trace_to_symbol
    return trace_to_symbol(x)


class Function:
    """Custom differentiable function (ref: autograd.py:364 mx.autograd.Function).

    Subclass and implement forward(self, *inputs) and backward(self, *out_grads),
    both operating on NDArrays with pause() semantics inside.
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *args):
        self._saved = args

    @property
    def saved_tensors(self):
        return self._saved

    def __call__(self, *inputs):
        from ..ndarray.ndarray import NDArray
        from ..ops.registry import Operator

        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)

        if is_recording():
            fn_self = self

            def vjp(ct):
                cts = (ct,) if not isinstance(ct, tuple) else ct
                with pause():
                    from ..ndarray.ndarray import NDArray as ND
                    ct_nd = [ND(c) for c in cts]
                    in_grads = fn_self.backward(*ct_nd)
                if not isinstance(in_grads, (list, tuple)):
                    in_grads = [in_grads]
                return tuple(g._read() for g in in_grads)

            fake_op = Operator("_custom_function", lambda *a: a,
                               num_inputs=len(inputs), num_outputs=len(outs))
            _record(fake_op, list(inputs), outs, vjp)
        return outputs

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError
