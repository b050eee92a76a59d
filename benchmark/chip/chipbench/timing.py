"""The measured window: a loop that keeps two steps in flight.

Before step *i* is enqueued the loss of step *i*-2 is waited for and the
clock is stamped.  A loop that blocks on every step measures a drained
pipeline, which is not how the product runs; one that never blocks has no
samples.  Step-time samples are the differences of consecutive stamps.
Throughput is the work of a block of consecutive steps over the median time
of the window's blocks (``median_block_rate``); the work completed between
the first and the last stamp over the time between them (``window_rate``)
is kept beside it, and ``lost_share`` is the distance between the two.
"""
import collections
import statistics
import time

IN_FLIGHT = 2
BLOCKS = 20                 # a 20 s window: about a second of work a block


def run_window(step, seconds, span, on_stamp=None,
               clock=time.perf_counter):
    """Call ``step()`` (which enqueues one train step and returns its loss,
    a device array) until ``seconds`` have passed, then drain.

    ``span(name)`` is a context manager that records a host span
    (``wait`` around each block, ``enqueue`` around each ``step()`` call).
    ``on_stamp(n)`` is called after the n-th stamp, while the pipeline still
    holds a step, and is where the traced sub-window is started and
    stopped.  Returns ``(stamps, losses)``: a stamp per completed step and
    the loss arrays in step order."""
    pending = collections.deque()
    stamps, losses = [], []

    def complete():
        loss = pending.popleft()
        with span("wait"):
            loss.block_until_ready()
        stamps.append(clock())
        losses.append(loss)
        if on_stamp is not None:
            on_stamp(len(stamps))

    end = clock() + seconds
    while clock() < end:
        if len(pending) == IN_FLIGHT:
            complete()
        with span("enqueue"):
            pending.append(step())
    while pending:
        complete()
    return stamps, losses


def window_rate(stamps, samples_per_step):
    """Samples completed a second over the window: the steps between the
    first and the last stamp over the time between them.  Every stall
    inside the window counts, a single one of the machine's as much as the
    program's own."""
    return (len(stamps) - 1) * samples_per_step / (stamps[-1] - stamps[0])


def block_seconds(stamps):
    """The window cut into consecutive blocks of equal work: ``(size,
    seconds)``, the steps in a block and the time each block took.  The
    steps left over at the end (fewer than ``size``) are in no block."""
    size = max(1, (len(stamps) - 1) // BLOCKS)
    edges = stamps[::size]
    return size, [b - a for a, b in zip(edges, edges[1:])]


def median_block_rate(stamps, samples_per_step):
    """Samples a second in the median block.  Whatever recurs at least once
    a block (a flush, a collection, a slow step in every ten) is in every
    block and counts in full, which a median of step times would hide; a
    stall that holds up fewer than half the blocks, however long, moves
    nothing.  The machine stalls a run for 6 to 9 s about once in 30
    (PERF.md): over the window that is a third of the rate, and one such
    run among six is more spread than any bound allows."""
    size, seconds = block_seconds(stamps)
    return size * samples_per_step / statistics.median(seconds)


def lost_share(stamps, steps=None):
    """The share of the time of ``steps`` (all of the window's by default)
    that the median block leaves out: 1 - the time they would take at the
    median block's pace over the time they took.  Near 0 in a window
    without a stall, a few tenths of a per cent either way."""
    steps = step_seconds(stamps) if steps is None else steps
    size, seconds = block_seconds(stamps)
    return 1.0 - len(steps) * statistics.median(seconds) / size / sum(steps)


def percentile(samples, q):
    """Nearest-rank percentile, ``q`` in 0..100, of a non-empty list."""
    ordered = sorted(samples)
    rank = max(1, -(-len(ordered) * q // 100))       # ceil
    return ordered[int(rank) - 1]


def step_seconds(stamps):
    return [b - a for a, b in zip(stamps, stamps[1:])]


def untraced_steps(stamps, traced_stamps=(None, None)):
    """Step-time samples with the traced sub-window left out: the steps
    between the stamps at which the profiler was started and stopped and
    two on each side (starting and stopping it stalls the loop)."""
    steps = step_seconds(stamps)
    first, last = traced_stamps
    if first is None:
        return steps
    last = len(stamps) if last is None else last
    # stamp n (1-based) closes step sample n-2 (0-based)
    return steps[:max(0, first - 3)] + steps[last + 1:]
