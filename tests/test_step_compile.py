"""graftstep: whole-step compiled training — fwd+bwd+fused update as ONE
donated XLA program (two at a kvstore boundary).

The contract under test (gluon/step_compile.py):

* **Parity** — compiled params AND optimizer states track the
  bucketed-eager ``record → backward → Trainer.step`` triple over ≥5
  steps for sgd / momentum / adam / mp-bf16, within the documented ULP
  tolerance (lr/wd/rescale ride as traced OPERANDS in the compiled
  program where graftfuse bakes constants; operands can shift
  fma-contraction by ~1 ULP per step — the EH104 convention, asserted
  via ``max_ulp_diff``'s monotone int-key oracle rather than allclose).
* **Guards** — shape change, dtype change, and param freeze/thaw each
  cost exactly ONE eager fallback step + ONE lazy retrace;
  ``set_learning_rate`` and a batch-size change cost ZERO retraces (the
  whole point of the operand layout); a static-shape loop shows zero
  retraces after step 2.
* **Boundary** — behind a store the cross-worker reduce stays at the
  program boundary via the existing ``reduce_many`` wire (labeled
  ``compiled_step`` in the flight recorder).
* **Satellites** — first-touch pull ordering
  (``Trainer.note_first_touch_order`` / ``GRAFT_BUCKET_ORDER=touch``),
  and the ``GRAFT_PREFETCH_DEPTH`` DataLoader knob.
"""
import os
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon
from incubator_mxnet_tpu import optimizer as opt
from incubator_mxnet_tpu.gluon.step_compile import (
    CompiledStep, max_ulp_diff, step_compile_enabled)
from incubator_mxnet_tpu.telemetry import blackbox

import jax.numpy as jnp


ULP_TOL = 8          # documented operand-vs-constant fma drift budget
N_PARAMS = 4
SHAPE = (1, 5)


def make_net(prefix, n_params=N_PARAMS, shape=SHAPE, dtype="float32"):
    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                for k in range(n_params):
                    setattr(self, "w%d" % k,
                            self.params.get("w%d" % k, shape=shape,
                                            dtype=dtype))

        def hybrid_forward(self, F, x, **ps):
            acc = None
            for k in range(n_params):
                y = (ps["w%d" % k] * ps["w%d" % k] * x).sum()
                acc = y if acc is None else acc + y
            return acc

    return Net(prefix=prefix)


def seed_net(net, seed=7):
    rng = np.random.RandomState(seed)
    net.initialize(ctx=mx.cpu())
    for name in sorted(net.collect_params()):
        p = net.collect_params()[name]
        p.data()._write(jnp.asarray(
            rng.uniform(-1.0, 1.0, p.shape).astype(np.float32)
        ).astype(p.data().dtype))


def make_pair(optimizer="sgd", opt_kw=None, n_params=N_PARAMS,
              shape=SHAPE, dtype="float32", kvstore=None, loss=None):
    """Identical (eager-twin, compiled) nets + trainers + the CompiledStep."""
    opt_kw = dict(opt_kw or {"learning_rate": 0.05})
    out = []
    for tag in ("e", "c"):
        net = make_net("sc%s_" % tag, n_params, shape, dtype)
        seed_net(net)
        kv = mx.kv.create(kvstore) if kvstore else None
        tr = gluon.Trainer(net.collect_params(), optimizer, dict(opt_kw),
                           kvstore=kv)
        out.extend([net, tr])
    net_e, tr_e, net_c, tr_c = out
    cstep = tr_c.compile_step(net_c, loss=loss, enabled=True)
    return net_e, tr_e, net_c, tr_c, cstep


def eager_step(net, tr, *args, loss=None, batch_size=1):
    with autograd.record():
        if loss is not None:
            out = loss(net(*args[:-1]), args[-1])
        else:
            out = net(*args)
    out.backward()
    tr.step(batch_size)
    return out


def _leaves(state):
    if state is None:
        return []
    if isinstance(state, (tuple, list)):
        out = []
        for s in state:
            out.extend(_leaves(s))
        return out
    return [state]


def assert_parity(net_e, tr_e, net_c, tr_c, tol=ULP_TOL):
    for ne, nc in zip(sorted(net_e.collect_params()),
                      sorted(net_c.collect_params())):
        ulp = max_ulp_diff(net_e.collect_params()[ne].data()._read(),
                           net_c.collect_params()[nc].data()._read())
        assert ulp <= tol, "weight %s diverged by %s ULP" % (ne, ulp)
    se, sc = tr_e._updaters[0].states, tr_c._updaters[0].states
    assert set(se) == set(sc)
    for i in se:
        for a, b in zip(_leaves(se[i]), _leaves(sc[i])):
            ulp = max_ulp_diff(a._read(), b._read())
            assert ulp <= tol, "state %d diverged by %s ULP" % (i, ulp)


def xbatch(rng, shape=(6, 5)):
    return mx.nd.array(rng.uniform(0.5, 1.5, shape).astype(np.float32))


# ---------------------------------------------------------------------------
# parity: ≥5 steps per optimizer family, zero retraces after step 2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer,opt_kw,dtype", [
    ("sgd", {"learning_rate": 0.05}, "float32"),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
     "float32"),
    ("adam", {"learning_rate": 0.01}, "float32"),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9,
             "multi_precision": True}, "bfloat16"),
], ids=["sgd", "momentum", "adam", "mp-bf16"])
def test_compiled_matches_eager_over_five_steps(optimizer, opt_kw, dtype):
    net_e, tr_e, net_c, tr_c, cstep = make_pair(optimizer, opt_kw,
                                                dtype=dtype)
    rng = np.random.RandomState(3)
    for _ in range(6):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert_parity(net_e, tr_e, net_c, tr_c)
    # step 1 fell back eager and traced lazily; steps 2..6 compiled with
    # ZERO further retraces — the acceptance criterion
    assert cstep.retraces == 1
    assert cstep.fallback_steps == 1
    assert cstep.compiled_steps == 5


def make_rowwise_net(prefix, n_params=N_PARAMS, shape=SHAPE):
    """Like make_net but per-ROW outputs (shape (N,)) so a batch-axis
    loss such as L2Loss has an axis to reduce over."""
    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                for k in range(n_params):
                    setattr(self, "w%d" % k,
                            self.params.get("w%d" % k, shape=shape))

        def hybrid_forward(self, F, x, **ps):
            acc = None
            for k in range(n_params):
                y = (ps["w%d" % k] * ps["w%d" % k] * x).sum(axis=1)
                acc = y if acc is None else acc + y
            return acc

    return Net(prefix=prefix)


def test_compiled_with_loss_fn_and_batch_size_change():
    """loss-callable call convention (last arg is the label) AND a
    batch-size change mid-loop: rescale rides as an operand, so no
    retrace — parity holds through both."""
    loss = gluon.loss.L2Loss()
    pair = []
    for tag in ("e", "c"):
        net = make_rowwise_net("scl%s_" % tag)
        seed_net(net)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        pair.extend([net, tr])
    net_e, tr_e, net_c, tr_c = pair
    cstep = tr_c.compile_step(net_c, loss=loss, enabled=True)
    rng = np.random.RandomState(5)
    for step in range(6):
        x = xbatch(rng)
        y = mx.nd.array(rng.uniform(-1, 1, (6,)).astype(np.float32))
        bs = 1 if step < 3 else 4
        eager_step(net_e, tr_e, x, y, loss=loss, batch_size=bs)
        cstep(x, y, batch_size=bs)
    assert cstep.retraces == 1, \
        "batch-size change retraced (rescale must be an operand)"
    assert_parity(net_e, tr_e, net_c, tr_c)


def test_kvstore_boundary_reduce_stays_on_the_wire():
    """Behind a store the compiled step splits at the program boundary:
    program A's bucket flats go through KVStore.reduce_many (the
    existing collective bracket, labeled), then the donated update
    program applies the reduced flats.  Parity vs the eager twin on the
    same store type, and the labeled collective lands in the flight
    recorder."""
    marker = time.time()
    net_e, tr_e, net_c, tr_c, cstep = make_pair(
        "sgd", {"learning_rate": 0.05, "momentum": 0.9},
        kvstore="dist_sync")
    rng = np.random.RandomState(11)
    for _ in range(6):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 1
    assert cstep.compiled_steps == 5
    assert_parity(net_e, tr_e, net_c, tr_c)
    evs = [e for e in blackbox.events()
           if e.get("kind") == "collective" and e.get("ts", 0) >= marker
           and e.get("data", {}).get("label") == "compiled_step"]
    assert len(evs) >= 5, \
        "compiled steps must ride the labeled reduce_many wire"


# ---------------------------------------------------------------------------
# guards: what retraces, what must not
# ---------------------------------------------------------------------------

def test_set_learning_rate_does_not_retrace():
    net_e, tr_e, net_c, tr_c, cstep = make_pair(
        "sgd", {"learning_rate": 0.05, "momentum": 0.9})
    rng = np.random.RandomState(7)
    for _ in range(3):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 1
    tr_e.set_learning_rate(0.005)
    tr_c.set_learning_rate(0.005)
    for _ in range(3):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 1, \
        "set_learning_rate retraced the compiled step (lr is an operand)"
    assert cstep.compiled_steps == 5
    assert_parity(net_e, tr_e, net_c, tr_c)


def test_shape_change_guard_one_retrace_each_then_cached():
    net_e, tr_e, net_c, tr_c, cstep = make_pair("sgd")
    rng = np.random.RandomState(9)
    for _ in range(2):
        x = xbatch(rng, (6, 5))
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 1
    for _ in range(2):                      # new input shape: ONE retrace
        x = xbatch(rng, (3, 5))
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 2
    assert cstep.fallback_steps == 2
    # back to the first shape: the entry is still cached — no retrace
    x = xbatch(rng, (6, 5))
    eager_step(net_e, tr_e, x)
    cstep(x)
    assert cstep.retraces == 2
    assert_parity(net_e, tr_e, net_c, tr_c)


def test_dtype_change_guard_misses():
    net_e, tr_e, net_c, tr_c, cstep = make_pair("sgd")
    rng = np.random.RandomState(13)
    for _ in range(2):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 1
    x16 = xbatch(rng).astype("float16")
    x16e = x16.copy()
    eager_step(net_e, tr_e, x16e)
    cstep(x16)
    assert cstep.retraces == 2, "input dtype change must re-trace"
    assert_parity(net_e, tr_e, net_c, tr_c)


def test_param_freeze_thaw_guard():
    """Freezing a param (grad_req write → null) moves it out of the
    trainable set → guard miss, one retrace; thawing it back re-hits the
    ORIGINAL cached entry — no third trace.  The eager twin freezes
    identically, so parity holds throughout."""
    net_e, tr_e, net_c, tr_c, cstep = make_pair(
        "sgd", {"learning_rate": 0.05})
    rng = np.random.RandomState(17)
    for _ in range(2):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 1

    def freeze(net, req):
        name = sorted(net.collect_params())[0]
        net.collect_params()[name].grad_req = req

    freeze(net_e, "null")
    freeze(net_c, "null")
    for _ in range(2):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 2, "freeze must re-trace (fewer diff inputs)"
    assert_parity(net_e, tr_e, net_c, tr_c)
    freeze(net_e, "write")
    freeze(net_c, "write")
    for _ in range(2):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.retraces == 2, "thaw back must re-hit the cached entry"
    assert_parity(net_e, tr_e, net_c, tr_c)


def test_kill_switch_and_recording_guard(monkeypatch):
    """GRAFT_STEP_COMPILE=0 runs every call on the bit-identical eager
    triple (zero compiled dispatches); calling a CompiledStep inside
    record() raises — the compiled step IS the whole triple."""
    monkeypatch.setenv("GRAFT_STEP_COMPILE", "0")
    assert not step_compile_enabled()
    assert step_compile_enabled(True)       # explicit override wins
    net_e, tr_e, net_c, tr_c, _ = make_pair("sgd")
    cstep = tr_c.compile_step(net_c)        # enabled=None → env decides
    rng = np.random.RandomState(19)
    for _ in range(3):
        x = xbatch(rng)
        eager_step(net_e, tr_e, x)
        cstep(x)
    assert cstep.compiled_steps == 0
    assert cstep.retraces == 0
    assert cstep.fallback_steps == 3
    # kill-switched steps ARE the eager triple: bit-identical, not ULP
    assert_parity(net_e, tr_e, net_c, tr_c, tol=0)
    with autograd.record():
        with pytest.raises(RuntimeError):
            cstep(xbatch(rng))


# ---------------------------------------------------------------------------
# satellite: first-touch pull ordering
# ---------------------------------------------------------------------------

def test_first_touch_order_recorded_and_fed_to_trainer():
    _net_e, _tr_e, net_c, tr_c, cstep = make_pair("sgd")
    rng = np.random.RandomState(29)
    cstep(xbatch(rng))                      # fallback + lazy trace
    assert cstep.forward_order is not None
    assert tr_c._first_touch_order == cstep.forward_order
    # the toy net touches w0..w3 in definition order
    names = [tr_c._params[i].name for i in cstep.forward_order]
    suffixes = [n.rsplit("w", 1)[-1] for n in names]
    assert suffixes == sorted(suffixes, key=int)
    assert len(cstep.forward_order) == N_PARAMS


def test_touch_perm_orders_pull_keys():
    params = [gluon.Parameter("tp%d" % k, shape=(2,)) for k in range(4)]
    for p in params:
        p.initialize(ctx=mx.cpu())
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore=None)
    assert tr._first_touch_order is None
    tr.note_first_touch_order((2, 0))
    assert tr._first_touch_order == (2, 0)
    # touched params first (in touch order), untouched after in index order
    assert tr._touch_perm([0, 1, 2, 3]) == [2, 0, 1, 3]
    # dedup + bounds filtering
    tr.note_first_touch_order((1, 1, 3, 99))
    assert tr._first_touch_order == (1, 3)


def test_bucket_order_touch_mode(monkeypatch):
    from incubator_mxnet_tpu import overlap
    monkeypatch.setenv("GRAFT_BUCKET_ORDER", "touch")
    assert overlap.bucket_order() == "touch"
    params = [gluon.Parameter("bo%d" % k, shape=(2,)) for k in range(3)]
    for p in params:
        p.initialize(ctx=mx.cpu())
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore=None)
    mode, sig_perm, build_perm = tr._plan_order()
    assert mode == "touch"
    assert build_perm == (0, 1, 2)          # nothing recorded yet
    tr.note_first_touch_order((2, 1))
    mode, sig_perm, build_perm = tr._plan_order()
    assert build_perm == (2, 1, 0)
    assert sig_perm == build_perm           # recording re-keys the plan


# ---------------------------------------------------------------------------
# satellite: GRAFT_PREFETCH_DEPTH
# ---------------------------------------------------------------------------

def test_prefetch_depth_knob(monkeypatch):
    from incubator_mxnet_tpu.gluon.data import DataLoader
    from incubator_mxnet_tpu.gluon.data.dataloader import (
        prefetch_depth_default)
    from incubator_mxnet_tpu.gluon.data.dataset import ArrayDataset
    assert prefetch_depth_default() == 2    # the double-buffer default
    monkeypatch.setenv("GRAFT_PREFETCH_DEPTH", "5")
    assert prefetch_depth_default() == 5
    monkeypatch.setenv("GRAFT_PREFETCH_DEPTH", "0")
    assert prefetch_depth_default() == 1    # floor: one in flight
    monkeypatch.setenv("GRAFT_PREFETCH_DEPTH", "junk")
    assert prefetch_depth_default() == 2
    ds = ArrayDataset(mx.nd.array(np.arange(32, dtype=np.float32)))
    loader = DataLoader(ds, batch_size=4, prefetch_device=False)
    try:
        assert loader.prefetch_depth() == 2
        loader.set_prefetch_depth(6)        # live override beats the env
        assert loader.prefetch_depth() == 6
        loader.set_prefetch_depth(0)
        assert loader.prefetch_depth() == 1
        out = [b for b in loader]
        assert len(out) == 8                # depth never changes content
    finally:
        loader.close()


# ---------------------------------------------------------------------------
# selftest tier (the run_lint hook) stays green
# ---------------------------------------------------------------------------

def test_module_selftest():
    from incubator_mxnet_tpu.gluon import step_compile
    assert step_compile.selftest() == []
