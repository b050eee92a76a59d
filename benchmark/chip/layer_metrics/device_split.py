"""The train step's device time from inside: forward, backward, update.

``fwd_device_ms_per_step`` / ``bwd_device_ms_per_step`` /
``update_device_ms_per_step``
    self time on the device, a step, of the ops the program staged under
    its ``xray:forward`` / ``xray:backward`` / ``xray:update`` scopes (the
    fused step, ``parallel/data_parallel.py``), or of the programs that are
    one phase whole (the Gluon loop's ``cachedop_forward``,
    ``cachedop_backward``, ``trainer_bucket_update``).
``device_unattributed_pct``
    the share of the busy time whose op has no phase: no scope in its
    ``op_name`` path, or a program the registry does not know.  Near 100
    in a run that read its step from a warm compile cache means the cache
    answered with an executable compiled before the scopes were there (its
    key leaves op metadata out).

The three and the unattributed time are a partition of every op's self
time, so they sum to ``trace.reduce``'s busy time; ``facts["device_split"]``
holds the residue, the time by program, the ten largest scopes below the
phases (Block names, ``flash_attention_bwd``: ``device_ms_by_block``; by
kind of Block, so that fifty BatchNorms add up: ``device_ms_by_block_kind``)
and the largest ops without a phase.

On the CPU rehearsal there is no device plane: the names are reported for
what the program's registry offers to join, and ``run.py`` blanks every
rehearsal value.
"""
from chipbench import program

_METRICS = {"fwd_device_ms_per_step": "forward",
            "bwd_device_ms_per_step": "backward",
            "update_device_ms_per_step": "update"}


def read(run):
    split = program.device_split(run)
    if split is None:
        if run.rehearse and not run.reduced:
            offered = program.offers(run)
            out = {m: 0.0 for m, phase in _METRICS.items()
                   if phase in offered}
            if "any" in offered:
                out["device_unattributed_pct"] = 0.0
            return out
        return {}
    steps, busy = split["steps"], split["busy_s"]
    per_step = lambda s: 1e3 * s / steps                # noqa: E731
    out = {m: per_step(split["phase_s"].get(phase, 0.0))
           for m, phase in _METRICS.items()}
    out["device_unattributed_pct"] = 100.0 * split["unattributed_s"] / busy
    summed = sum(split["phase_s"].values()) + split["unattributed_s"]
    largest = lambda d: sorted(d.items(), key=lambda kv: -kv[1])   # noqa: E731
    kinds = {}
    for scope, s in split["scope_s"].items():
        kind = program.kind_of(scope)
        kinds[kind] = kinds.get(kind, 0.0) + s
    ops = run.recorded["ops"]
    run.facts["device_split"] = {
        "ms_per_step_by_phase": {p: per_step(s)
                                 for p, s in split["phase_s"].items()},
        "unattributed_ms_per_step": per_step(split["unattributed_s"]),
        "busy_ms_per_step": per_step(busy),
        "residue_pct_of_busy": 100.0 * (busy - summed) / busy,
        "ms_per_step_by_program": {n or "(no module event)": per_step(s)
                                   for n, s in split["program_s"].items()},
        "device_ms_by_block": [[name, per_step(s)]
                               for name, s in largest(split["scope_s"])[:10]],
        "device_ms_by_block_kind": [[name, per_step(s)]
                                    for name, s in largest(kinds)[:10]],
        # name [category, result type] as the trace has them
        "unattributed_ops": [
            ["%s %s" % (name, ops.get(name, "")), per_step(s)]
            for name, s in largest(split["unattributed_op_s"])[:5]],
        "programs": split["programs"]}
    return out
