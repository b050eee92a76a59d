"""The control of a state-space configuration's own: the plain reference
with the scan's state and decay held in bfloat16, read against the reference
itself.

    python3 benchmark/chip/control_state.py --workload <cell> --seeds 1 2 3 \
        [--rehearse]

``control.py``'s two controls round the operands of the matrix products and
Adam's moments; neither reaches the recurrence, which has no matmul in it.
Here the configuration's ``reference`` is given ``scan_state_dtype``
``"bfloat16"`` (``configs/phi4_flash.py::_recurrence`` rounds the state and
the decay to it at every step, forward and in its adjoint), the first steps
are followed as ``chipbench/first_steps.py`` follows them, and a line a seed
gives the three gaps beside the limits a run holds the program to, and a
fourth, ``block_f32``: the control's logits against the reference's on the
rows ``run.py::reference_check`` holds the Block's float32 forward to, by
its measure (the largest difference over the largest logit).  A state in
bfloat16 moves the first steps less than the program's own bf16 matrix
products do, so no limit on them can tell it from the program; the forward
check, which the program passes in float32, can.  The control is *not
correct* when one of the four is over its limit.  The benchmark's runs
never call this: it is how the limits were read (PERF.md).
"""
import gc
import json
import sys

import run as bench
from chipbench import first_steps, inputs

CONTROL = {"scan_state_dtype": "bfloat16"}


def forward_gap(jax, module, sizes, start, sample, device):
    """The control's logits on ``sample`` against the reference's, as
    ``run.py::reference_check`` reads the Block's against them."""
    import jax.numpy as jnp
    params, sample = jax.device_put((start, sample), device)
    with jax.default_matmul_precision("highest"):
        want, got = (jax.jit(lambda p, s, c=changed: module.reference(
            dict(sizes, **c), p, s))(params, sample)
            for changed in ({}, CONTROL))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def main(argv=None, root=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.seed, args.seconds, args.trace = args.seeds[0], 0.0, 0
    run = bench.open_run(args, root)
    jax, module, sizes, traffic = run.jax, run.module, run.sizes, run.traffic
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    if not first_steps.applies(sizes) or "mamba_d_state" not in sizes:
        raise SystemExit("control_state: cell %s has no state-space layer "
                         "whose first steps a plain optimizer follows"
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
        cut = len(module.PREFIX)
        start = {n[cut:]: np.asarray(p.data()._read(), np.float32)
                 for n, p in net.collect_params().items()}
        del net
        gc.collect()
        rows = module.check_rows(sizes, traffic)
        want, got = (first_steps.reference_side(
            jax, module, dict(sizes, **changed), start, x, y, rows,
            run.devices[0]) for changed in ({}, CONTROL))
        gaps, where = first_steps.compare(got, want)
        limits = {k: module.TOLERANCE[k + suffix] for k in gaps}
        gaps["block_f32"], where["block_f32"] = forward_gap(
            jax, module, sizes, start, x[:rows], run.devices[0]), "logits"
        limits["block_f32"] = module.TOLERANCE["block_f32"]
        lines.append({"workload": args.workload, "seed": seed,
                      "control": "bf16_scan_state", "gaps": gaps, "at": where,
                      "limits": limits,
                      "correct": all(gaps[k] <= limits[k] for k in gaps),
                      "losses": got["losses"],
                      "reference_losses": want["losses"]})
        print("[control] %s" % json.dumps(lines[-1]), flush=True)
    return lines


if __name__ == "__main__":
    main()
    sys.exit(0)
