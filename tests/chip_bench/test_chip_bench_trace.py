"""The reduction from a profiler trace to numbers, on traces small enough
to work out by hand, and the FLOP functions against hand counts.

``SMALL`` is written out below: one device plane, two programs a step,
nested host spans.  The recorded traces under ``benchmark/chip/testdata/``,
one a cell, are cut from real chip traces of this benchmark; their answers
are checked by a second, slower way of computing them.
"""
import gzip
import importlib
import json
import pathlib

import pytest

TESTDATA = (pathlib.Path(__file__).resolve().parents[2]
            / "benchmark" / "chip" / "testdata")
US = 1000                       # the traces below think in microseconds


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


def _waits(ends_us):
    return [_ev("bench:wait", e - 1, 1) for e in ends_us]


# Waits end at 0 and 50 (skipped: starting the profiler drained the pipeline),
# then at 200, 300 and 400: the window is [200, 400] us and holds 2 steps.
SMALL = {
    "ops": {"fusion.1": ["convolution fusion", "jit(step)/conv"],
            "fusion.2": ["loop fusion", "jit(step)/bn"],
            "all-reduce.3": ["all-reduce", "jit(step)/psum"],
            "custom-call.4": ["custom-call", "jit(step)/flash_attention"]},
    "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_step", 150, 60),       # starts before the window
                _ev("jit_step", 210, 80), _ev("jit_update", 295, 5),
                _ev("jit_step", 310, 80), _ev("jit_update", 395, 10)]},
            {"name": "XLA Ops", "events": [
                _ev("fusion.1", 190, 20),        # clipped to [200, 210]
                _ev("fusion.1", 210, 40),        # [210, 250]
                _ev("all-reduce.3", 240, 30),    # [240, 270], 10 hidden
                _ev("fusion.2", 280, 10),        # [280, 290]
                _ev("custom-call.4", 310, 40),   # [310, 350]
                _ev("all-reduce.3", 350, 10),    # [350, 360], all exposed
                _ev("fusion.2", 395, 10)]}]},    # clipped to [395, 400]
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [_ev("fusion.1", 200, 100)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": _waits([0, 50, 200, 300, 400]) + [
                _ev("bench:enqueue", 255, 60),       # [255, 315]
                _ev("bench:update", 290, 20)]}]},    # [290, 310], nested
    ],
}


def test_small_trace_by_hand(chip_run):
    tr = chip_run.trace
    r = tr.reduce(SMALL)
    assert r["steps"] == 2 and r["devices"] == 2
    assert r["window_s"] == pytest.approx(200e-6)
    # device 0 busy: [200,270] + [280,290] + [310,360] + [395,400] = 135 us;
    # device 1: 100 us; the mean is 117.5 us
    assert r["busy_s"] == pytest.approx(117.5e-6)
    assert r["idle_share"] == pytest.approx(1 - 117.5 / 200)
    # four programs start inside the window on device 0
    assert r["programs"] == 4
    # collectives on device 0: [240,270] + [350,360] = 40 us, of which
    # [240,250] runs under fusion.1: 30 us exposed
    assert r["collective_s"] == pytest.approx(40e-6)
    assert r["collective_exposed_s"] == pytest.approx(30e-6)
    # per-operation seconds are self time, means over the two devices: on
    # device 0 fusion.1 runs [200,210] and [210,250], of which [240,250] is
    # under the all-reduce that starts inside it and counts there
    assert r["op_s"]["fusion.1"] == pytest.approx((10 + 30 + 100) / 2 * 1e-6)
    assert r["op_s"]["all-reduce.3"] == pytest.approx((30 + 10) / 2 * 1e-6)
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"])
    assert r["op_s"]["custom-call.4"] == pytest.approx(20e-6)
    assert r["category_s"]["loop fusion"] == pytest.approx(7.5e-6)
    # device 0 idles in [270,280], [290,310], [360,395]: 65 us.  [270,280]
    # lies in enqueue; [290,310] in update, the innermost span there, but
    # for the wait [299,300], which is shorter still; [360,395] in no span
    gaps = r["idle_gaps_s"]
    assert gaps["bench:enqueue"] == pytest.approx(10e-6)
    assert gaps["bench:update"] == pytest.approx(19e-6)
    assert gaps["bench:wait"] == pytest.approx(1e-6)
    assert gaps["(no bench span)"] == pytest.approx(35e-6)
    assert sum(gaps.values()) == pytest.approx(65e-6)

    b = tr.breakdown(r, SMALL["ops"])
    assert b["device_ops"][0] == ["category:convolution fusion",
                                  pytest.approx(70e-6)]
    assert ["fusion.1 [convolution fusion jit(step)/conv]",
            pytest.approx(70e-6)] in b["device_ops"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "(no bench span)"


def test_self_time_counts_nested_operations_once(chip_run):
    """A ``while`` holds its body's operations on the same line."""
    events = [("while", 0, 100), ("a", 10, 40), ("b", 40, 90),
              ("inner", 50, 60), ("after", 100, 130)]
    own = dict(chip_run.trace.self_times(events))
    assert own == {"while": 20, "a": 30, "b": 40, "inner": 10, "after": 30}
    assert sum(own.values()) == 130


def test_names_and_categories_from_hlo_text(chip_run):
    parse = chip_run.trace.parse_instruction
    assert parse(
        "%fusion.46 = (f32[64]{0:T(128)S(1)}, bf16[256,64,112,112]{0,1,3,2:"
        "T(8,128)(2,1)}) fusion(bf16[256,64,112,112]{0,1,3,2} %gte.2205), "
        "kind=kLoop, calls=%fused_computation.70") == (
            "%fusion.46", "fusion:kLoop", "bf16[256,64,112,112]")
    assert parse(
        "%flash_attention_pallas.3 = bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}"
        " custom-call(bf16[128,2048,128]{2,1,0} %bitcast.562), "
        'custom_call_target="tpu_custom_call", operand_layout_cons={}') == (
            "%flash_attention_pallas.3", "custom-call:tpu_custom_call",
            "bf16[128,2048,128]")
    assert parse("%copy-start.698 = (f32[256]{0:T(256)}, u32[]{:S(2)}) "
                 "copy-start(f32[256]{0:T(256)S(1)} %gte.1565)") == (
                     "%copy-start.698", "copy-start", "f32[256]")
    assert parse("jit_step(6823471011644943011)") == (
        "jit_step(6823471011644943011)", "", "")


# Cut from chip traces of this benchmark (TPU v5 lite, PR 25's own runs): the
# first few steps of each traced sub-window, times rebased.  What the
# reduction must find in them.
RECORDED = {
    "resnet50_fused_b256": dict(steps=2, programs=2, step_ms=(108.0, 108.5),
                                top_category="fusion:kOutput",
                                modules={"dp_train_step"}),
    "opt6b7_fused_s2048": dict(steps=3, programs=3, step_ms=(268.5, 269.5),
                               top_category="fusion:kOutput",
                               modules={"dp_train_step"}),
    "opt6b7_fused_adam": dict(steps=3, programs=3, step_ms=(282.5, 284.0),
                              top_category="fusion:kOutput",
                              modules={"dp_train_step"}),
    # the first step after the profiler started: the host was ahead
    "resnet50_gluon_b128": dict(steps=1, programs=35, step_ms=(108.0, 110.0),
                                top_category="fusion:kOutput",
                                modules={"cachedop_forward",
                                         "cachedop_backward",
                                         "trainer_bucket_update"}),
    # two of the four chips' planes
    "resnet50_fused_dp4_b1024": dict(steps=1, programs=1, devices=2,
                                     step_ms=(108.5, 109.5),
                                     top_category="fusion:kOutput",
                                     collective_ms=(1.40, 1.50),
                                     modules={"dp_train_step"}),
}


def _reader(bench_catalog, name):
    (reader,) = [r for r in bench_catalog.readers()
                 if r.__name__.endswith(name)]
    return reader


def _recorded(cell):
    with gzip.open(TESTDATA / (cell + ".trace.json.gz"), "rt") as f:
        return json.load(f)


def _sweep_busy(events, window):
    """Busy nanoseconds by a sweep over sorted boundaries, the slow way."""
    lo, hi = window
    marks = []
    for _, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            marks += [(s, 1), (e, -1)]
    busy = depth = 0
    last = None
    for t, step in sorted(marks):
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    return busy


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_chip_trace(chip_run, cell):
    tr, want = chip_run.trace, RECORDED[cell]
    recorded = _recorded(cell)
    r = tr.reduce(recorded)
    assert (r["steps"], r["programs"], r["devices"]) == (
        want["steps"], want["programs"], want.get("devices", 1))
    lo, hi = want["step_ms"]
    assert lo < 1e3 * r["window_s"] / r["steps"] < hi
    # the window, found again from the waits by hand: the third wait's end
    # to the last wait's end
    waits = sorted(s + d for p in recorded["planes"] for l in p["lines"]
                   for n, s, d in l["events"] if n == "bench:wait")
    window = (waits[2], waits[-1])
    assert r["window_s"] == pytest.approx((window[1] - window[0]) / 1e9)
    ops = next(l["events"] for p in recorded["planes"]
               if p["name"] == "/device:TPU:0"
               for l in p["lines"] if l["name"] == "XLA Ops")
    if r["devices"] == 1:       # busy is a mean over the devices, the
        #                         idle gaps are the first device's
        assert r["busy_s"] == pytest.approx(_sweep_busy(ops, window) / 1e9,
                                            rel=1e-9)
        assert sum(r["idle_gaps_s"].values()) == pytest.approx(
            r["window_s"] - r["busy_s"], rel=1e-6)
    # these steps chain through donated buffers: the device hardly idles
    assert 0 <= r["idle_share"] < 0.002
    # self time counts every busy nanosecond once, nested loops or not
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=1e-9)
    assert sum(r["category_s"].values()) == pytest.approx(r["busy_s"],
                                                          rel=1e-9)
    assert max(r["category_s"], key=r["category_s"].get) == \
        want["top_category"]
    # one chip: no collective.  Four: the gradient and BatchNorm all-reduces
    # run on the main line, one after another, with nothing beside them
    lo, hi = want.get("collective_ms", (0.0, 0.0))
    assert lo <= 1e3 * r["collective_s"] <= hi
    assert r["collective_exposed_s"] == pytest.approx(r["collective_s"])
    b = tr.breakdown(r, recorded["ops"])
    assert 1 <= len(b["device_ops"]) <= 10 and 1 <= len(b["idle_gaps"]) <= 10
    assert all(len(name) < 120 for name, _ in b["device_ops"])
    # the programs by the names the program's registry knows them under
    program_of = importlib.import_module("chipbench.program").program_of
    ran = {program_of(name) for p in tr.device_planes(recorded)
           for name, _, _ in tr._line(p, tr.MODULES_LINE)}
    assert want["modules"] <= ran


@pytest.mark.parametrize("cell", ["opt6b7_fused_s2048", "opt6b7_fused_adam"])
def test_flash_kernels_in_the_recorded_opt_traces(chip_run, bench_catalog,
                                                  cell):
    """Two layers, so a step holds two forward calls and two of each
    backward kernel, each by the name its reader looks for: 1.7 to 1.8 ms a
    forward call against 0.70 ms at the bf16 roof, 4.5 ms a layer's
    backward against 2.44."""
    fwd = _reader(bench_catalog, "flash_fwd")
    bwd = _reader(bench_catalog, "flash_bwd")
    recorded = _recorded(cell)
    r = chip_run.trace.reduce(recorded)
    forward = {n: s for n, s in r["op_s"].items() if fwd.KERNEL.search(n)}
    backward = {n: s for n, s in r["op_s"].items() if bwd.KERNELS.search(n)}
    assert sorted(forward) == ["%flash_attention_pallas.2",
                               "%flash_attention_pallas.3"]
    assert sorted(backward) == [
        "%flash_attention_bwd_dkv.4", "%flash_attention_bwd_dkv.5",
        "%flash_attention_bwd_dq.4", "%flash_attention_bwd_dq.5"]
    assert all(recorded["ops"][n][0] == "custom-call:tpu_custom_call"
               for n in list(forward) + list(backward))
    assert 1.70 < 1e3 * sum(forward.values()) / r["steps"] / 2 < 1.80
    per_layer_ms = 1e3 * sum(backward.values()) / r["steps"] / 2
    assert 4.45 < per_layer_ms < 4.60
    # as the reader reports it: 54 % of the roof, well under 100
    flops, _ = bwd.needs(batch=4, heads=32, seq=2048, head_dim=128,
                         dtype_bytes=2)
    assert 53.0 < 100 * flops / 197e12 / (per_layer_ms / 1e3) < 55.0


def test_interval_arithmetic(chip_run):
    tr = chip_run.trace
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert tr.total([(1, 4), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_a_trace_with_nothing_to_read_gives_nothing(chip_run):
    """The CPU rehearsal's trace has no device plane; a trace cut before the
    third wait has no window.  Readers then report nothing."""
    tr = chip_run.trace
    host_only = {"ops": {}, "planes": [SMALL["planes"][2]]}
    assert tr.reduce(host_only) is None
    no_window = {"ops": {}, "planes": [
        SMALL["planes"][0],
        {"name": "/host:CPU", "lines": [
            # one wait after the two that are skipped
            {"name": "main", "events": _waits([0, 50, 100])}]}]}
    assert tr.reduce(no_window) is None


def test_percentiles_and_the_traced_steps_left_out(chip_run):
    t = chip_run.timing
    assert t.percentile(list(range(1, 101)), 50) == 50
    assert t.percentile(list(range(1, 101)), 90) == 90
    assert t.percentile([3.0], 90) == 3.0
    stamps = [float(i) for i in range(20)]
    assert t.step_seconds(stamps) == [1.0] * 19
    # the profiler started after stamp 8 and stopped after stamp 12
    kept = t.untraced_steps(stamps, (8, 12))
    assert len(kept) == 5 + (19 - 13)
    assert t.untraced_steps(stamps) == [1.0] * 19


def _stamps(steps):
    stamps = [0.0]
    for s in steps:
        stamps.append(stamps[-1] + s)
    return stamps


# 200 steps of 0.1 s, 256 samples each: blocks of 10 steps
_EVEN = [0.1] * 200
_WINDOWS = {
    # nothing happens: the median block, the window and the median step
    # agree
    "even": (_EVEN, 2560.0, 2560.0, 2560.0),
    # every tenth step takes five times as long (a flush, a collection):
    # it is in every block and counts in full; the median step hides it
    "recurring": ([0.5 if i % 10 == 9 else 0.1 for i in range(200)],
                  256 * 10 / 1.4, 256 * 10 / 1.4, 2560.0),
    # the machine holds the run up once, for 6 s: the window loses a
    # quarter of its rate, the median block nothing
    "one_stall": ([6.1 if i == 77 else 0.1 for i in range(200)],
                  2560.0, 256 * 200 / 26.0, 2560.0),
    # a slow stretch over a third of the steps, twice as slow: still fewer
    # than half the blocks
    "slow_stretch": ([0.2 if 50 <= i < 117 else 0.1 for i in range(200)],
                     2560.0, 256 * 200 / 26.7, 2560.0),
    # more than half the blocks slow: that is the rate
    "mostly_slow": ([0.2 if i < 120 else 0.1 for i in range(200)],
                    1280.0, 256 * 200 / 32.0, 1280.0),
}


@pytest.mark.parametrize("case", sorted(_WINDOWS))
def test_the_window_and_its_median_block(chip_run, case):
    """The end-to-end rate is all the work of the window over all of its
    time (``window_rate``): a stall counts.  The median block's rate, the
    per-layer statistic beside it, counts in full what recurs at least once
    a block (which the median step would hide) and nothing of a stall that
    holds up fewer than half the blocks; ``lost_share`` is the distance
    between the two."""
    t = chip_run.timing
    steps, block, window, median_step = _WINDOWS[case]
    stamps = _stamps(steps)
    size, seconds = t.block_seconds(stamps)
    assert (size, len(seconds)) == (10, 20)
    assert sum(seconds) == pytest.approx(stamps[-1])
    assert t.median_block_rate(stamps, 256) == pytest.approx(block)
    # 200 steps from the window's start to the last of its work
    assert t.window_rate(200, 256, stamps[0], stamps[-1]) == pytest.approx(
        window)
    assert 256 / t.percentile(t.step_seconds(stamps), 50) == pytest.approx(
        median_step)
    assert t.lost_share(stamps) == pytest.approx(1.0 - window / block)


@pytest.mark.parametrize("steps, size, blocks", [
    (186, 9, 20),       # ResNet fused: 6 steps left over at the end
    (55, 2, 27),        # OPT
    (19, 1, 19),        # fewer steps than blocks: a block is a step
    (2, 1, 2),          # the rehearsal's shortest windows
])
def test_blocks_are_of_equal_work(chip_run, steps, size, blocks):
    t = chip_run.timing
    stamps = _stamps([0.1] * steps)
    got_size, seconds = t.block_seconds(stamps)
    assert (got_size, len(seconds)) == (size, blocks)
    assert seconds == pytest.approx([0.1 * size] * blocks)
    assert t.median_block_rate(stamps, 128) == pytest.approx(1280.0)
    # over a part of the steps (a traced run leaves the traced ones out)
    assert t.lost_share(stamps, [0.1] * 7 + [0.8]) == pytest.approx(
        1.0 - 0.8 / 1.5)


def test_the_loop_keeps_two_steps_in_flight(chip_run):
    import contextlib
    t = chip_run.timing
    log, now = [], [0.0]

    class Loss:
        def __init__(self, n):
            self.n = n

        def block_until_ready(self):
            log.append(("wait", self.n))

    def step():
        now[0] += 1.0
        log.append(("enqueue", len([e for e in log if e[0] == "enqueue"])))
        return Loss(log[-1][1])

    @contextlib.contextmanager
    def span(name):
        yield

    def settle():
        # the last step's work outlasts its loss (the MXNet loop's loss
        # comes out of the forward program)
        now[0] += 0.5
        log.append(("settle",))

    stamps, losses, (start, done) = t.run_window(
        step, 4.5, span, settle=settle, clock=lambda: now[0])
    assert [e for e in log[:5]] == [("enqueue", 0), ("enqueue", 1),
                                    ("wait", 0), ("enqueue", 2), ("wait", 1)]
    assert [l.n for l in losses] == list(range(len(losses)))
    assert len(stamps) == len(losses) == 5      # every enqueued step drained
    # the window runs from its start to when its work is done, not from the
    # first loss to the last: every step counts, and the time to the end
    assert log[-1] == ("settle",) and (start, done) == (0.0, 5.5)
    assert stamps[-1] == 5.0
    assert t.window_rate(len(stamps), 8, start, done) == pytest.approx(
        5 * 8 / 5.5)


# ---------------------------------------------------------------------------
# model FLOPs against hand counts
# ---------------------------------------------------------------------------

def test_resnet50_flops_by_hand(bench_catalog):
    sizes, module = bench_catalog.config("resnet50_v1")
    macs = module.forward_macs_per_sample(sizes)
    # stem 7x7/2: 112*112 * 3*64*49
    stem = 112 * 112 * 3 * 64 * 49
    # stage 1's first block at 56x56: 1x1 64->64, 3x3 64->64, 1x1 64->256,
    # and the 1x1 shortcut 64->256
    block = 56 * 56 * (64 * 64 + 64 * 64 * 9 + 64 * 256 + 64 * 256)
    # stage 1's two other blocks: 1x1 256->64, 3x3, 1x1 64->256
    rest = 2 * 56 * 56 * (256 * 64 + 64 * 64 * 9 + 64 * 256)
    assert stem == 118013952 and block + rest == 667942912
    # the zoo's v1 puts the stride on the leading 1x1: 3.86 GMAC an image
    # (the variant with the stride on the 3x3 is the often quoted 4.09)
    assert macs == 3857973248
    assert macs > stem + block + rest
    assert module.train_flops_per_sample(sizes, {}) == 6.0 * macs
    toy, _ = bench_catalog.config("resnet50_v1", rehearse=True)
    # thumbnail ResNet-18 at 32x32: the stem 3x3 3->64 and the dense layer
    assert module.forward_macs_per_sample(toy) > 32 * 32 * 27 * 64 + 512 * 10


def test_opt_flops_by_hand(bench_catalog):
    sizes, module = bench_catalog.config("opt_6b7_l2")
    d = sizes["hidden_size"]
    assert sizes["ffn_dim"] == 4 * d
    # a layer is 12 d^2 matmul parameters; the head is vocab * d
    assert module.matmul_params(sizes) == 2 * 12 * d * d + 50272 * d
    per_token = module.train_flops_per_sample(sizes, {"seq_len": 2048})
    assert per_token == 6.0 * (24 * d * d + 50272 * d) + 6.0 * 2048 * d * 2
    # the head is a third of the matmul FLOPs at depth 2 (1/33 at depth 32)
    head = 50272 * d / module.matmul_params(sizes)
    assert 0.32 < head < 0.35


def test_flash_kernel_needs_by_hand(bench_catalog):
    reader = _reader(bench_catalog, "flash_fwd")
    flops, nbytes = reader.needs(batch=4, heads=32, seq=2048, head_dim=128,
                                 dtype_bytes=2)
    assert flops == 2 * 4 * 32 * 2048 * 2048 * 128        # two matmuls, half
    assert nbytes == 4 * 4 * 32 * 2048 * 128 * 2
