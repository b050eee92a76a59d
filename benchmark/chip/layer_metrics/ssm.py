"""The state-space layer (``gluon.nn.Mamba`` over ``ops/ssm.py``) and the
gated memory unit, from the scopes the program stages them under.

``ssm_scan_fwd_ms_per_step``, ``ssm_scan_bwd_ms_per_step``
    self time of every op under the scope ``ssm_scan`` (the selective scan's
    kernel calls, or the ``jnp`` form's fusions: whatever implements it), a
    step, all layers; forward and backward told apart by the ``xray:`` phase
    of the op's path;
``ssm_scan_fwd_roofline``, ``ssm_scan_bwd_roofline``
    what the scan needs at the chip's published peaks over that time, in %;
``ssm_proj_ms_per_step``
    self time of every op under ``ssm_proj``: ``W_in``, the convolution and
    its ``silu``, ``W_x``, ``W_dt``, the softplus, the gate, ``W_out``,
    forward and backward;
``gmu_ms_per_step``
    the same under ``gmu``: the gated memory unit's two projections and its
    gate.

What one call needs (``needs``) on B rows of L steps, C channels of N
states: forward x, delta (B L C each) and B, C (B L N each) read and y
(B L C) written, in the operands' dtype; backward g read, the operands read
again, dx, ddelta, dB, dC written in that dtype and dA (C N), dD (C) in
float32.  The arithmetic (about 7 operations a channel a state a step, one
pass forward and two backward) against the MXU's rate is smaller still, so
the bytes bound it.  The work is the VPU's and the EUP's, which have no
published peak: the share reads low, and a share over 100 % is a wrong
count.  Calls a step: the ``mamba`` entries of ``layer_types``.

Left out where the configuration has no ``mamba_d_state``, where the program
has no registry of its ops (an older commit), and each metric where nothing
ran under its scope.  On the CPU rehearsal a metric is named where the
registry has an op under its scope.
"""
from chipbench import peaks, program, scopes

SCAN, PROJ, GMU = "ssm_scan", "ssm_proj", "gmu"
_BY_SCOPE = {"ssm_proj_ms_per_step": PROJ, "gmu_ms_per_step": GMU}
_PHASES = {"ssm_scan_fwd": "forward", "ssm_scan_bwd": "backward"}
OPS_A_STATE = 7                 # mul, exp's argument, fma, mul-add into y ...


def needs(batch, length, channels, states, dtype_bytes):
    """((FLOPs, bytes) forward, (FLOPs, bytes) backward) of one call."""
    wide = batch * length * channels * dtype_bytes
    narrow = batch * length * states * dtype_bytes
    once = OPS_A_STATE * batch * length * channels * states
    return ((once, 3 * wide + 2 * narrow),
            (2 * once, 5 * wide + 4 * narrow + 4 * channels * (states + 1)))


def read(run):
    sizes = run.sizes
    if "mamba_d_state" not in sizes:
        return {}
    paths = scopes.op_paths(run)
    if paths is None:
        return {}
    r = run.reduced
    if not r or not r["steps"] or run.peaks is None:
        if not (run.rehearse and not r):
            return {}
        # the rehearsal's names: what the registry has an op under
        out = {m: 0.0 for m, scope in _BY_SCOPE.items()
               if any(scopes.under(p, scope) for p in paths.values())}
        for stem, phase in _PHASES.items():
            if any(scopes.under(p, SCAN) and program.phase_of(p) == phase
                   for p in paths.values()):
                out.update({stem + "_ms_per_step": 0.0,
                            stem + "_roofline": 0.0})
        return out
    steps = r["steps"]
    seconds = dict.fromkeys((*_BY_SCOPE, *_PHASES), 0.0)
    for _, path, s in scopes.traced_ops(run, paths):
        for metric, scope in _BY_SCOPE.items():
            if scopes.under(path, scope):
                seconds[metric] += s
        if scopes.under(path, SCAN):
            for stem, phase in _PHASES.items():
                if program.phase_of(path) == phase:
                    seconds[stem] += s
    out = {m: 1e3 * seconds[m] / steps for m in _BY_SCOPE if seconds[m]}
    traffic = run.traffic
    needed = needs(traffic["batch_per_chip"], traffic["seq_len"],
                   sizes["mamba_expand"] * sizes["hidden_size"],
                   sizes["mamba_d_state"],
                   peaks.DTYPE_BYTES[traffic["dtype"]])
    calls = list(sizes["layer_types"]).count("mamba")
    facts = {"chunk": scopes.gauge(run, "graft_ssm_scan_chunk"),
             "state_elems": scopes.gauge(run, "graft_ssm_state_elems")}
    for stem, (flops, nbytes) in zip(_PHASES, needed):
        scan_s = seconds[stem] / steps
        if not scan_s or not calls:
            continue
        least, roof = peaks.least_time(flops, nbytes, run.peaks)
        out[stem + "_ms_per_step"] = 1e3 * scan_s
        out[stem + "_roofline"] = 100.0 * least * calls / scan_s
        facts[stem] = {"roof": roof, "least_ms_per_call": 1e3 * least,
                       "calls_per_step": calls}
    run.facts["ssm_scan"] = facts
    return out
