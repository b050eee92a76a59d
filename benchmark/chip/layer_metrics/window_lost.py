"""``window_lost_pct`` and ``median_block_samples_per_s``: the window with
its stalls told apart from its pace.

``samples_per_s_per_chip`` is every sample of the window over all of its
time, so a stall inside the window, the machine's or the program's, lowers
it.  ``median_block_samples_per_s`` is the rate of the window's median
block of steps (``timing.median_block_rate``), a chip's share of it: the
pace when nothing stalls, which a stall that holds up fewer than half the
blocks does not move.  ``window_lost_pct`` is the share of the window's
time that went to such stalls: 1 - the time the steps would take at the
median block's pace over the time they took, over the steps outside the
traced sub-window (starting and stopping the profiler stalls the loop).  A
few hundredths of a per cent either way in a steady window; 30 where the
machine held the run up for 6 s of its 20.  Every run's ``[facts]`` line
has both over all of its steps under ``window``.
"""
from chipbench import timing


def read(run):
    steps = run.step_samples
    if len(steps) < 10:
        return {}
    return {"window_lost_pct": 100.0 * timing.lost_share(run.stamps, steps),
            "median_block_samples_per_s": timing.median_block_rate(
                run.stamps, run.samples_per_step) / run.chips}
