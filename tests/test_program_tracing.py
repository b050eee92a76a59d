"""The train step opened from inside: phase scopes and program names in
both train paths, the lazy program registry (``telemetry.programs()``), and
the one span primitive with its record (``telemetry.spans()``) and its
``mx:`` annotation in the JAX profiler's trace.

Every program here is read from the optimized HLO of a fresh compile: JAX's
persistent cache leaves op metadata out of its key, so a cached executable
may carry the scopes of an older build of the same program.
"""
import contextlib
import glob
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, engine, gluon, telemetry
from incubator_mxnet_tpu.gluon.block import functionalize
from incubator_mxnet_tpu.ops.attention import flash_attention
from incubator_mxnet_tpu.parallel import DataParallelTrainer, make_mesh
from incubator_mxnet_tpu.parallel.data_parallel import pure_optimizer
from incubator_mxnet_tpu.telemetry import blackbox, tracing, xray

PHASES = {"forward", "backward", "update"}


@pytest.fixture
def fresh_compiles():
    """No persistent compile cache: the HLO read is of this build."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _mlp(prefix, batchnorm=False):
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu"))
        if batchnorm:
            net.add(gluon.nn.BatchNorm())
        net.add(gluon.nn.Dense(5))
    mx.random.seed(11)
    net.initialize(mx.init.Xavier())
    return net


def _batch(rows=8, width=12):
    rs = np.random.RandomState(5)
    return (rs.rand(rows, width).astype(np.float32),
            (np.arange(rows) % 5).astype(np.int32))


def _fused(net, **kw):
    return DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=make_mesh({"dp": 1}, jax.devices()[:1]), **kw)


# ---------------------------------------------------------------------------
# A. scopes, names and the registry
# ---------------------------------------------------------------------------

def test_fused_step_ops_carry_one_of_three_phases(fresh_compiles):
    net = _mlp("pt_scopes_", batchnorm=True)
    x, y = _batch()
    trainer = _fused(net)
    trainer.step(x, y)
    prog = telemetry.programs()["dp_train_step"]
    assert prog.phase is None and prog.error is None
    scoped = {op: path for op, path in prog.ops.items() if "xray:" in path}
    # every scoped op maps to exactly one of the three phases, by the
    # first scope of its path (a backward op's path wraps the forward's)
    phases = {op: xray.phase_of(path) for op, path in scoped.items()}
    assert set(phases.values()) == PHASES
    assert prog.scope_map() == phases
    wrapped = [p for p in scoped.values() if "transpose(jvp(" in p]
    assert wrapped and all(xray.phase_of(p) == "backward" for p in wrapped)
    # the program is named for the trace's modules line
    hlo = trainer.compiled_step(x, y).as_text()
    assert hlo.startswith("HloModule jit_dp_train_step")
    assert xray._norm_module("jit_dp_train_step(6823471011644943011)") \
        == "dp_train_step"


def test_block_names_are_in_the_scope_path_only_when_traced(fresh_compiles):
    net = _mlp("pt_blocks_", batchnorm=True)
    x, y = _batch()
    # the eager path stages nothing and opens no scope
    seen = []
    real = jax.named_scope
    try:
        jax.named_scope = lambda name: seen.append(name) or real(name)
        net(mx.nd.array(x)).asnumpy()
    finally:
        jax.named_scope = real
    assert not [n for n in seen if n.startswith("pt_blocks_")]
    trainer = _fused(net)
    trainer.step(x, y)
    paths = list(telemetry.programs()["dp_train_step"].ops.values())
    for block in ("pt_blocks_dense0", "pt_blocks_batchnorm0",
                  "pt_blocks_dense1"):
        assert any("xray:forward/jvp(%s)" % block in p for p in paths), block
        assert any("xray:backward/transpose(jvp(%s))" % block in p
                   for p in paths), block


def test_fused_step_equals_the_value_and_grad_formulation_bit_for_bit():
    """The step as the parent commit wrote it (one ``jax.value_and_grad``,
    then the update loop) on the same weights and batch."""
    x, y = _batch()
    net = _mlp("pt_parity_")
    net(mx.nd.array(x[:1]))                 # shapes
    fn, params = functionalize(net, mx.nd.array(x), train=True)
    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()
    init, update = pure_optimizer("sgd", momentum=0.9)

    def parent_step(params, opt_state, x, y, lr):
        def forward_loss(p):
            out = fn(p, x)
            out = out[0] if isinstance(out, (tuple, list)) else out
            per_sample = loss_blk(mx.nd.NDArray(out), mx.nd.NDArray(y))
            return jnp.mean(per_sample._read())
        loss, grads = jax.value_and_grad(forward_loss)(params)
        new = {n: update(params[n], grads[n], opt_state[n], lr)
               for n in params}
        return ({n: v[0] for n, v in new.items()},
                {n: v[1] for n, v in new.items()}, loss)

    state = {n: init(v) for n, v in params.items()}
    want_p, want_s, want_loss = jax.jit(parent_step)(
        params, state, jnp.asarray(x), jnp.asarray(y), jnp.float32(0.1))
    trainer = _fused(net)
    loss = trainer.step(x, y)
    assert np.asarray(loss) == np.asarray(want_loss)
    for name, value in trainer._params.items():
        np.testing.assert_array_equal(np.asarray(value),
                                      np.asarray(want_p[name]), name)
        np.testing.assert_array_equal(np.asarray(trainer._opt_state[name][0]),
                                      np.asarray(want_s[name][0]), name)


def test_compiled_step_and_registry_agree_with_the_private_trees():
    """What ``benchmark/chip/drivers/fused.py::temp_bytes`` reads through
    the trainer's private argument trees is what the two public ways give."""
    net = _mlp("pt_temp_", batchnorm=True)
    x, y = _batch()
    t = _fused(net, dtype="bfloat16")
    t.step(x, y)
    xs, ys = t._prepare_inputs(x, y, jax.sharding.PartitionSpec("dp"))
    private = t.compile(xs, ys).lower(
        t._params, t._opt_state, t._rng_key, xs, ys,
        t._lr_dev).compile().memory_analysis().temp_size_in_bytes
    assert t.compiled_step(x, y).memory_analysis().temp_size_in_bytes \
        == private
    assert telemetry.programs()["dp_train_step"].memory["temp_bytes"] \
        == private


def test_flash_backward_scope_reaches_the_scans_body(fresh_compiles):
    """``jax.grad`` of ``flash_attention`` on the CPU path: one scan (the
    statistics arrive from the forward), and every op of its body sits
    under ``flash_attention_bwd``."""
    q = jnp.ones((1, 2, 384, 8), jnp.float32)       # 3 key blocks of 128

    def loss(q, k, v):
        return flash_attention(q, k, v, True).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    bodies = set(re.findall(r"\bwhile\(.*body=%?([\w.\-]+)", hlo))
    assert len(bodies) == 1, bodies
    computation, in_body = None, {b: [] for b in bodies}
    for line in hlo.splitlines():
        head = re.match(r"^%?([\w.\-]+) \(.*\{$", line)
        if head:
            computation = head.group(1)
        op = re.search(r'op_name="([^"]*)"', line)
        if op and computation in in_body:
            in_body[computation].append(op.group(1))
    for body, paths in in_body.items():
        # all but a constant the compiler sank into the loop (the cotangent
        # of the caller's sum); the pass rebuilds the scores' exponentials
        scoped = [p for p in paths if "flash_attention_bwd" in p]
        assert any(p.endswith("/while/body/closed_call/exp")
                   for p in scoped), body
        assert len(scoped) >= 0.8 * len(paths), (body, [
            p for p in paths if "flash_attention_bwd" not in p])
    # the forward keeps the name the flash_fwd reader looks for
    fwd = jax.jit(lambda q: flash_attention(q, q, q, True)).lower(
        q).compile().as_text()
    assert "flash_attention_bwd" not in fwd
    assert "flash_attention_reference" in fwd


def test_gluon_loop_programs_are_named_and_whole_phase(fresh_compiles):
    net = _mlp("pt_gluon_")
    net.hybridize()
    x, y = _batch()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="device")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net(mx.nd.array(x)), mx.nd.array(y))
        loss.backward()
        trainer.step(x.shape[0])
    progs = telemetry.programs()
    assert progs["cachedop_forward"].phase == "forward"
    assert progs["cachedop_backward"].phase == "backward"
    assert progs["trainer_bucket_update"].phase == "update"
    for name in ("cachedop_forward", "cachedop_backward",
                 "trainer_bucket_update"):
        assert progs[name].memory["temp_bytes"] >= 0, progs[name].error
    # a whole-phase program's ops are there for a reader to find Blocks in
    assert any("pt_gluon_dense0" in p
               for p in progs["cachedop_forward"].ops.values())


def test_registering_compiles_nothing_until_a_reader_asks():
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    net = _mlp("pt_lazy_")
    x, y = _batch()
    trainer = _fused(net)
    trainer.step(x, y)
    before = len(compiles)
    assert before                           # the step itself compiled
    trainer.step(x, y)
    prog = telemetry.programs()["dp_train_step"]    # listing is not reading
    assert prog.phase is None
    assert prog._ops is None and prog._memory is None
    assert len(compiles) == before
    # the first reader lowers and compiles (JAX may answer from memory),
    # prints the HLO and parses it, once
    assert prog.ops and prog.memory
    ops = prog._ops
    assert telemetry.programs()["dp_train_step"].ops is ops


def test_a_program_that_is_gone_reads_empty_and_says_why():
    fn = jax.jit(lambda a: a + 1)
    xray.register_program("pt_gone", fn, (jnp.ones(3),), phase="forward")
    del fn
    prog = telemetry.programs()["pt_gone"]
    assert prog.ops == {} and prog.memory == {}
    assert prog.error == "the program is gone"


# ---------------------------------------------------------------------------
# B. the span primitive
# ---------------------------------------------------------------------------

def test_span_record_has_start_end_parent_and_step():
    t0 = telemetry.spans()[-1][2] if telemetry.spans() else 0.0
    with tracing.phase_span("step", step=41):
        with tracing.phase_span("place"):
            pass
        with tracing.phase_span("dispatch"):
            with tracing.phase_span("engine_flush", {"cause": "read"}):
                pass
    got = {s[0]: s for s in telemetry.spans(since=t0)
           if s[4] == 41}
    assert set(got) == {"step", "place", "dispatch", "engine_flush"}
    assert got["step"][3] is None
    assert got["place"][3] == "step" and got["dispatch"][3] == "step"
    assert got["engine_flush"][3] == "dispatch"
    for name, start, end, _parent, step in got.values():
        assert got["step"][1] <= start <= end <= got["step"][2], name
        assert step == 41
    # oldest first, by the time they closed
    names = [s[0] for s in telemetry.spans(since=got["step"][1])]
    assert names[-4:] == ["place", "engine_flush", "dispatch", "step"]


def test_fused_steps_spans_share_the_steps_id():
    net = _mlp("pt_ids_")
    x, y = _batch()
    trainer = _fused(net)
    trainer.step(x, y)
    mark = telemetry.spans()[-1][2]
    trainer.step(x, y)
    trainer.step(x, y)
    by_step = {}
    for name, _s, _e, parent, step in telemetry.spans(since=mark):
        by_step.setdefault(step, []).append((name, parent))
    assert sorted(by_step) == [2, 3]
    for spans in by_step.values():
        assert sorted(spans) == [("dispatch", "step"), ("place", "step"),
                                 ("step", None)]


def test_gluon_loop_spans_fwd_bwd_kvstore_update_and_flushes():
    net = _mlp("pt_loop_")
    net.hybridize()
    x, y = _batch()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="device")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def step():
        with autograd.record():
            loss = loss_fn(net(mx.nd.array(x)), mx.nd.array(y))
        loss.backward()
        trainer.step(x.shape[0])

    step()
    mark = telemetry.spans()[-1][2]
    step()
    spans = telemetry.spans(since=mark)
    assert {"fwd", "bwd", "kvstore", "update"} <= {s[0] for s in spans}
    # one step of the lens: the loop's spans share its id
    assert len({s[4] for s in spans
                if s[0] in ("fwd", "bwd", "kvstore", "update")}) == 1
    # a flush inside a bulk scope is a span with its parent
    with tracing.phase_span("update"):
        with engine.bulk(4):
            (mx.nd.ones((2, 2)) + 1).asnumpy()
    flushes = [s for s in telemetry.spans(since=mark)
               if s[0] == "engine_flush"]
    assert flushes and flushes[-1][3] == "update"


def test_span_records_stay_bounded_and_ride_the_dump():
    held = blackbox._ring_size()
    try:
        blackbox.configure(size=16)
        for _ in range(100):
            with tracing.phase_span("kvstore"):
                pass
        assert len(telemetry.spans()) == 16
        doc = blackbox.snapshot()
        assert blackbox.validate_dump(doc) == []
        assert len(doc["spans"]) == 16
        assert doc["spans"][-1][0] == "kvstore"
        assert set(doc["perf_anchor"]) == {"perf_s", "wall_s"}
    finally:
        blackbox.configure(size=held)
    # the recorder's switch is the spans' switch
    n = len(telemetry.spans())
    blackbox.set_enabled(False)
    try:
        with tracing.phase_span("kvstore"):
            pass
    finally:
        blackbox.set_enabled(None)
    assert len(telemetry.spans()) == n


def test_nested_spans_do_not_count_twice_in_the_step_journal():
    with blackbox.step_journal("trainer", batch_size=2):
        with tracing.phase_span("update"):
            with tracing.phase_span("engine_flush", {"cause": "read"}):
                pass
    step = [e["data"] for e in blackbox.events() if e["kind"] == "step"][-1]
    assert set(step["phases"]) == {"update"}


def test_spans_land_in_the_jax_profilers_trace(tmp_path):
    """``mx:update`` on a host plane, beside the device events."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.phase_span("update"):
            jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "mx:update" in names


# ---------------------------------------------------------------------------
# C. one way to watch a step: the loops leave spans and a journal, and no
#    thread of the package's behind them
# ---------------------------------------------------------------------------

def _square_params(prefix, n=3):
    rs = np.random.RandomState(0)
    ps = []
    for k in range(n):
        p = gluon.Parameter("%s%d" % (prefix, k), shape=(8, 8))
        p.initialize(ctx=mx.cpu())
        p.data()._write(rs.randn(8, 8).astype(np.float32))
        ps.append(p)
    return ps


def _param_loop(prefix, n, bulk):
    ps = _square_params(prefix)
    trainer = gluon.Trainer(ps, "sgd", {"learning_rate": 0.01},
                            kvstore=mx.kv.create("local"))
    for _ in range(n):
        with engine.bulk(64) if bulk else contextlib.nullcontext():
            with autograd.record():
                loss = sum((p.data() * p.data()).sum() for p in ps)
            loss.backward()
        trainer.step(1)
    ps[-1].data().asnumpy()


def _loop_deferred_bulk(n):
    _param_loop("wt_bulk", n, bulk=True)
    return "step", {"bwd", "kvstore", "update"}


def _loop_unbulked_eager(n):
    _param_loop("wt_eager", n, bulk=False)
    return "step", {"bwd", "kvstore", "update"}


def _loop_trainer_local_kvstore(n):
    net = _mlp("wt_gluon_")
    net.hybridize()
    x, y = _batch()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            kvstore=mx.kv.create("local"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(n):
        with autograd.record():
            loss = loss_fn(net(mx.nd.array(x)), mx.nd.array(y))
        loss.backward()
        trainer.step(x.shape[0])
    loss.asnumpy()
    return "step", {"fwd", "bwd", "kvstore", "update"}


def _loop_module_update(n):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="wt_fc")
    m = mx.mod.Module(mx.sym.SoftmaxOutput(fc, name="softmax"),
                      context=mx.cpu())
    m.bind(data_shapes=[("data", (6, 10))],
           label_shapes=[("softmax_label", (6,))])
    m.init_params(initializer=mx.init.Xavier())
    m.init_optimizer(optimizer="sgd")
    rs = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(6, 10).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 4, (6,)).astype(np.float32))])
    for _ in range(n):
        m.forward_backward(batch)
        m.update()
    m.get_outputs()[0].asnumpy()
    return "step", {"fwd", "bwd", "update"}


def _loop_serving_batches(n):
    from incubator_mxnet_tpu import serving
    net = _mlp("wt_serve_")
    net.hybridize()
    x, _y = _batch()
    with serving.Server(max_batch=4, max_wait_ms=1) as srv:
        srv.load("m", block=net, example=mx.nd.array(x[:1]))
        for _ in range(n):
            srv.submit("m", x[0]).get(timeout=60.0)
    return "serve_batch", set()


def _package_threads():
    return {t for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("graft")}


@pytest.mark.parametrize("loop", [
    _loop_deferred_bulk, _loop_unbulked_eager, _loop_trainer_local_kvstore,
    _loop_module_update, _loop_serving_batches],
    ids=lambda f: f.__name__[len("_loop_"):])
def test_loop_leaves_spans_and_no_watcher_thread(loop):
    n = 4
    before = _package_threads()
    blackbox._ring.clear()
    mark = time.perf_counter()
    kind, names = loop(n)
    # the journal: one event a step, stamped with ids that rise by one,
    # and none of them carries a second decomposition of the step
    closed = [e["data"] for e in blackbox.events() if e["kind"] == kind]
    assert len(closed) == n
    ids = [e["step"] for e in closed]
    assert ids == list(range(ids[0], ids[0] + n))
    assert not any("lens" in e for e in closed)
    # the spans: every step's carry the id its journal closed with
    by_step = {}
    for name, _t0, _t1, parent, step in telemetry.spans(since=mark):
        if parent is None:
            by_step.setdefault(step, set()).add(name)
    for step in ids:
        assert names <= by_step.get(step, set()), (step, by_step)
    # the threads: whatever the loop started has ended with it
    deadline = time.monotonic() + 5.0
    while _package_threads() - before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not {t.name for t in _package_threads() - before}
