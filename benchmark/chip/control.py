"""The control of ``chipbench/first_steps.py``: the reference put in the
program's place in a lower precision, read against the reference itself.

    python3 benchmark/chip/control.py --workload <cell> --seeds 1 2 3 \
        [--rehearse]

For each seed the cell's weights and batch are made as a run makes them,
and the first steps are followed by the plain reference as it is and by
two controls: both operands of every matrix product, forward and backward,
rounded to fp8 (e4m3, a scale a tensor), which is the precision below the
bf16 the configuration computes in; Adam's moments kept in bf16.  A line a
seed and control gives the three gaps ``first_steps.compare`` reads beside
the limits a run holds the program to; a control is *not correct* when one of
them is over its limit.  The benchmark's runs never call this: it is how
the limits in the configuration's module were read (PERF.md), and
``tests/chip_bench`` keeps it at the rehearsal's sizes.
"""
import gc
import json
import sys

import run as bench
from chipbench import first_steps, inputs

CONTROLS = {"fp8_matmuls": {"matmuls_as": "float8_e4m3fn"},
            "bf16_moments": {"moments_as": "bfloat16"}}


def main(argv=None, root=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", choices=sorted(CONTROLS),
                    default=sorted(CONTROLS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.seed, args.seconds, args.trace = args.seeds[0], 0.0, 0
    run = bench.open_run(args, root)
    jax, module, sizes, traffic = run.jax, run.module, run.sizes, run.traffic
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    if not first_steps.applies(sizes):
        raise SystemExit("control: cell %s makes no first steps against a "
                         "plain optimizer" % args.workload)
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
        want = first_steps.reference_side(jax, module, sizes, start, x, y,
                                          rows, run.devices[0])
        for name in args.controls:
            got = first_steps.reference_side(jax, module, sizes, start, x, y,
                                             rows, run.devices[0],
                                             **CONTROLS[name])
            gaps, where = first_steps.compare(got, want)
            limits = {k: module.TOLERANCE[k + suffix] for k in gaps}
            lines.append({"workload": args.workload, "seed": seed,
                          "control": name, "gaps": gaps, "at": where,
                          "limits": limits,
                          "correct": all(gaps[k] <= limits[k] for k in gaps),
                          "losses": got["losses"],
                          "reference_losses": want["losses"]})
            print("[control] %s" % json.dumps(lines[-1]), flush=True)
    return lines


if __name__ == "__main__":
    main()
    sys.exit(0)
