"""``control_state.py`` for a configuration whose scan is a delta rule: the
plain reference with the recurrence's state and decay held in bfloat16, read
against the reference itself.

    python3 benchmark/chip/control_delta_state.py --workload <cell> \
        --seeds 1 2 3 [--rehearse]

``control_state.py`` takes only a configuration with a ``mamba_d_state``;
this takes one with ``linear_num_value_heads``, whose ``reference`` reads
the same key, ``scan_state_dtype`` (``configs/qwen3_next.py::_recurrence``
rounds the state and the decay to it at every token, forward and in its
adjoint).  The rest is that file's: the first steps followed as
``chipbench/first_steps.py`` follows them, a line a seed with the three gaps
beside the limits a run holds the program to and a fourth, ``block_f32``
(``control_state.forward_gap``).  The control is *not correct* when one of
the four is over its limit.  The benchmark's runs never call this: it is how
the limits were read (PERF.md).
"""
import gc
import json
import sys

import control_state
import run as bench
from chipbench import first_steps, inputs

CONTROL = control_state.CONTROL


def main(argv=None, root=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--forward-only", action="store_true",
                    help="the fourth gap alone: no first steps")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.seed, args.seconds, args.trace = args.seeds[0], 0.0, 0
    run = bench.open_run(args, root)
    jax, module, sizes, traffic = run.jax, run.module, run.sizes, run.traffic
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    if not first_steps.applies(sizes) or \
            "linear_num_value_heads" not in sizes:
        raise SystemExit("control_delta_state: cell %s has no delta-rule "
                         "layer whose first steps a plain optimizer follows"
                         % args.workload)
    suffix = "_rehearsal" if args.rehearse else ""
    lines = []
    for seed in args.seeds:
        args.seed = seed
        net = bench.seeded_net(run, dict(sizes, **sizes["first_loss_with"]))
        x, y = inputs.make_batch(
            jax, seed, traffic["batch_per_chip"] * run.chips,
            module.sample_shape(sizes, traffic), sizes,
            NamedSharding(run.mesh, PartitionSpec("dp")))
        with run.mx.autograd.train_mode():      # deferred shapes
            net(run.mx.nd.NDArray(jax.device_put(np.asarray(x[:1]),
                                                 run.devices[0])))
        cut = len(module.PREFIX)
        start = {n[cut:]: np.asarray(p.data()._read(), np.float32)
                 for n, p in net.collect_params().items()}
        del net
        gc.collect()
        rows = module.check_rows(sizes, traffic)
        gaps, where, limits, losses = {}, {}, {}, {}
        if not args.forward_only:
            want, got = (first_steps.reference_side(
                jax, module, dict(sizes, **changed), start, x, y, rows,
                run.devices[0]) for changed in ({}, CONTROL))
            gaps, where = first_steps.compare(got, want)
            limits = {k: module.TOLERANCE[k + suffix] for k in gaps}
            losses = {"losses": got["losses"],
                      "reference_losses": want["losses"]}
        gaps["block_f32"], where["block_f32"] = control_state.forward_gap(
            jax, module, sizes, start, x[:rows], run.devices[0]), "logits"
        limits["block_f32"] = module.TOLERANCE["block_f32"]
        lines.append(dict({"workload": args.workload, "seed": seed,
                           "control": "bf16_scan_state", "gaps": gaps,
                           "at": where, "limits": limits,
                           "correct": all(gaps[k] <= limits[k]
                                          for k in gaps)}, **losses))
        print("[control] %s" % json.dumps(lines[-1]), flush=True)
    return lines


if __name__ == "__main__":
    main()
    sys.exit(0)
