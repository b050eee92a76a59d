"""The measured window: a loop that keeps two steps in flight.

Before step *i* is enqueued the loss of step *i*-2 is waited for and the
clock is stamped.  A loop that blocks on every step measures a drained
pipeline, which is not how the product runs; one that never blocks has no
samples.  Step-time samples are the differences of consecutive stamps.
Throughput is all the work of the window over all of its time: every step
it enqueued over the time from its start, on an empty pipeline, to the
moment the last of that work is done (``window_rate``).  A loss can be
ready before its step is (the MXNet loop's comes out of the forward
program, ahead of the backward and the update), so the end is stamped
after ``settle()``, the job's own barrier, not at the last loss.  The rate
of the window's median block of steps (``median_block_rate``), which a
stall does not move, is kept beside it for the per-layer readers, and
``lost_share`` is the distance between the two.
"""
import collections
import statistics
import time

IN_FLIGHT = 2
BLOCKS = 20                 # a 20 s window: about a second of work a block


def run_window(step, seconds, span, on_stamp=None, settle=None,
               clock=time.perf_counter):
    """Call ``step()`` (which enqueues one train step and returns its loss,
    a device array) until ``seconds`` have passed, then drain.

    ``span(name)`` is a context manager that records a host span
    (``wait`` around each block, ``enqueue`` around each ``step()`` call).
    ``on_stamp(n)`` is called after the n-th stamp, while the pipeline still
    holds a step, and is where the traced sub-window is started and
    stopped.  ``settle()`` returns when everything the steps enqueued is
    done.  Returns ``(stamps, losses, (start, done))``: a stamp per
    completed step, the loss arrays in step order, and the clock at the
    window's start and once its work was done."""
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

    start = clock()
    end = start + seconds
    while clock() < end:
        if len(pending) == IN_FLIGHT:
            complete()
        with span("enqueue"):
            pending.append(step())
    while pending:
        complete()
    if settle is not None:
        settle()
    return stamps, losses, (start, clock())


def window_rate(steps, samples_per_step, start, done):
    """Samples a second over the window: the work of all its ``steps`` over
    the time from its start to when that work was done.  Filling the
    pipeline counts, and every stall inside the window, a single one of the
    machine's as much as the program's own: the end-to-end rate."""
    return steps * samples_per_step / (done - start)


def block_seconds(stamps):
    """The window cut into consecutive blocks of equal work: ``(size,
    seconds)``, the steps in a block and the time each block took.  The
    steps left over at the end (fewer than ``size``) are in no block."""
    size = max(1, (len(stamps) - 1) // BLOCKS)
    edges = stamps[::size]
    return size, [b - a for a, b in zip(edges, edges[1:])]


def median_block_rate(stamps, samples_per_step):
    """Samples a second in the median block: the pace of the window with
    its stalls left out, a per-layer statistic beside the end-to-end rate.
    Whatever recurs at least once a block (a flush, a collection, a slow
    step in every ten) is in every block and counts in full, which a median
    of step times would hide; a stall that holds up fewer than half the
    blocks, however long, moves nothing."""
    size, seconds = block_seconds(stamps)
    return size * samples_per_step / statistics.median(seconds)


def lost_share(stamps, steps=None):
    """The share of the time of ``steps`` (all of the window's by default)
    that went to what the median block leaves out: 1 - the time they would
    take at the median block's pace over the time they took.  Near 0 in a
    window without a stall, a few tenths of a per cent either way."""
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
