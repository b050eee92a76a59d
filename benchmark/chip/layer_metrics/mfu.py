"""``mfu_pct``: model FLOP/s utilisation.  The FLOPs the forward and
backward passes of one step require (the configuration's own function of
its shapes; recomputed operations do not count) over the median step time,
over chips x the published bf16 peak.  An end-to-end utilisation, not a
kernel's roofline share."""
from chipbench import timing


def read(run):
    if run.peaks is None:
        return {}
    if len(run.step_samples) < 10:
        return {}
    flops = (run.module.train_flops_per_sample(run.sizes, run.traffic)
             * run.samples_per_step)
    step_s = timing.percentile(run.step_samples, 50)
    return {"mfu_pct": 100.0 * flops / step_s
            / (run.chips * run.peaks["bf16_flops"])}
