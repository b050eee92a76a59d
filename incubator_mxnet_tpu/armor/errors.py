"""graftarmor typed failure classes.

Every failure the armor subsystem can surface is a *typed* exception
carrying the evidence a supervisor needs to act: which RPC command gave
up after how many attempts, which collective timed out against which
dead ranks, which checkpoint failed its manifest.  Catching
:class:`ArmorError` catches all of them; nothing here imports anything,
so any layer (the watchdog thread included) can raise these without
circular-import risk.
"""

__all__ = ["ArmorError", "FaultInjectedError", "PSUnavailableError",
           "CollectiveTimeoutError", "CheckpointCorruptError",
           "ShardOwnershipError", "MembershipChangedError",
           "QuiesceTimeoutError"]


class ArmorError(RuntimeError):
    """Base of every typed robustness failure."""


class FaultInjectedError(ArmorError):
    """An injected ``kind=error`` fault (armor/faults.py) — chaos, not a
    real failure; the site name travels in ``.site`` so post-mortems can
    tell the two apart without parsing messages."""

    def __init__(self, site, detail=None):
        super().__init__("injected fault at %r%s"
                         % (site, (" (%s)" % detail) if detail else ""))
        self.site = site


class PSUnavailableError(ArmorError):
    """A parameter-service RPC exhausted its retry budget.  ``cmd`` is
    the RPC verb, ``attempts`` how many tries were burned, ``dead_ranks``
    whatever the heartbeat table knew when we gave up (may be empty —
    the server itself being gone reports no table at all)."""

    def __init__(self, cmd, attempts, last_error=None, dead_ranks=()):
        msg = ("parameter service unavailable: %r failed after %d "
               "attempt%s" % (cmd, attempts, "" if attempts == 1 else "s"))
        if dead_ranks:
            msg += "; dead ranks: %s" % list(dead_ranks)
        if last_error is not None:
            msg += " (last error: %r)" % (last_error,)
        super().__init__(msg)
        self.cmd = cmd
        self.attempts = attempts
        self.last_error = last_error
        self.dead_ranks = tuple(dead_ranks)


class CollectiveTimeoutError(ArmorError):
    """A collective/RPC bracket outlived the watchdog timeout and
    GRAFT_WATCHDOG_ESCALATE asked for a raise instead of a hang.  Names
    the stuck site, its age, and the dead ranks the heartbeat table
    reported — the fail-fast alternative to waiting for SIGKILL."""

    def __init__(self, site, age_s, timeout_s, dead_ranks=(), detail=None):
        msg = ("collective %r stuck for %.1fs (watchdog timeout %.1fs)"
               % (site, age_s, timeout_s))
        if dead_ranks:
            msg += "; dead ranks: %s" % list(dead_ranks)
        if detail:
            msg += "; detail: %r" % (detail,)
        super().__init__(msg)
        self.site = site
        self.age_s = age_s
        self.timeout_s = timeout_s
        self.dead_ranks = tuple(dead_ranks)
        self.detail = detail


class CheckpointCorruptError(ArmorError):
    """A snapshot failed structural validation or its manifest hash —
    the loader refuses to resume from it (resume falls back to the
    previous snapshot; model.resume_from_checkpoint skips the epoch)."""

    def __init__(self, path, reason):
        super().__init__("checkpoint %s is not loadable: %s" % (path, reason))
        self.path = str(path)
        self.reason = reason


class ShardOwnershipError(ArmorError):
    """A snapshot's ZeRO-1 shard layout does not match the resuming
    trainer's: a sharded snapshot landing on an unsharded trainer, an
    unsharded snapshot landing on a sharded one, or two sharded runs
    with different shard counts/axes.  Optimizer state is partitioned
    by bucket ownership, so silently restoring across layouts would
    leave most shards untrained; the saved and current specs travel in
    ``.saved`` / ``.current`` for supervisors to reconcile.  When the
    mismatch crosses a graftelastic membership epoch, ``.epoch`` names
    the snapshot's epoch (restore across a changed world size is only
    legal with GRAFT_ELASTIC=1, which re-partitions deterministically
    instead of raising this)."""

    def __init__(self, saved, current, epoch=None):
        def _fmt(spec):
            if not spec:
                return "unsharded"
            return "%s-sharded n=%s" % (spec.get("axis"), spec.get("n"))
        msg = ("shard layout mismatch: snapshot is %s but this trainer is "
               "%s — re-launch with the snapshot's GRAFT_SHARD_OPTIMIZER "
               "topology (or retrain)" % (_fmt(saved), _fmt(current)))
        if epoch is not None:
            msg += ("; snapshot was taken at membership epoch %d — set "
                    "GRAFT_ELASTIC=1 to re-partition shard state across "
                    "the epoch boundary" % int(epoch))
        super().__init__(msg)
        self.saved = dict(saved) if saved else None
        self.current = dict(current) if current else None
        self.epoch = None if epoch is None else int(epoch)


class MembershipChangedError(ArmorError):
    """The cluster membership moved under a caller (graftelastic): a
    collective, rejoin stream, or barrier observed a membership epoch
    other than its own — the world it was issued against no longer
    exists.  Carries both epochs plus the departed/joined rank sets so
    a supervisor can quiesce, re-partition, and retry at the new epoch
    instead of mispairing the wire."""

    def __init__(self, old_epoch, new_epoch, departed=(), joined=(),
                 detail=None):
        msg = ("membership changed: epoch %d -> %d"
               % (int(old_epoch), int(new_epoch)))
        if departed:
            msg += "; departed ranks: %s" % sorted(departed)
        if joined:
            msg += "; joined ranks: %s" % sorted(joined)
        if detail:
            msg += " (%s)" % (detail,)
        super().__init__(msg)
        self.old_epoch = int(old_epoch)
        self.new_epoch = int(new_epoch)
        self.departed = tuple(sorted(departed))
        self.joined = tuple(sorted(joined))
        self.detail = detail


class QuiesceTimeoutError(CollectiveTimeoutError):
    """``DistKVStore.quiesce()`` could not drain the in-flight async
    pushes/pulls within its budget — the duplex wire is stuck (dead
    server, hung RPC), so a re-partition that remapped key ranges now
    would race the stale traffic.  A :class:`CollectiveTimeoutError`
    subtype: the same supervisors that handle watchdog escalation
    handle this."""

    def __init__(self, site, age_s, timeout_s, pending=0, dead_ranks=()):
        super().__init__(site, age_s, timeout_s, dead_ranks=dead_ranks,
                         detail="%d in-flight operation%s undrained"
                         % (pending, "" if pending == 1 else "s"))
        self.pending = int(pending)
