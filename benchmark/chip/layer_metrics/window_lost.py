"""``window_lost_pct``: what the end-to-end rate leaves out.

``samples_per_s_per_chip`` is the rate of the window's median block of
steps, so a stall that holds up fewer than half the blocks moves it by
nothing.  This is the share of the window's time that went to such stalls:
1 - the time the steps would take at the median block's pace over the time
they took, over the steps outside the traced sub-window (starting and
stopping the profiler stalls the loop).  A few hundredths of a per cent
either way in a steady window; 30 where the machine held the run up for
6 s of its 20.  Every run's ``[facts]`` line has the same number over all
of its steps under ``window``.
"""
from chipbench import timing


def read(run):
    steps = run.step_samples
    if len(steps) < 10:
        return {}
    return {"window_lost_pct": 100.0 * timing.lost_share(run.stamps, steps)}
