"""Static analysis for the op registry and the bulking engine.

Two cooperating passes (SURVEY §7: ONE registry serves eager, autograd
and symbolic execution — so one malformed registration corrupts all
three at once, and nothing checked the contracts until a user hit them):

* ``contracts`` — the op-contract linter (pass 1): verifies every
  registered Operator against its fcompute signature and AST.  CLI:
  ``python -m incubator_mxnet_tpu.analysis.graftlint``.
* ``engine_check`` — the strict-mode engine verifier (pass 2): hazard
  structures raised by ``engine.py`` when ``GRAFT_ENGINE_CHECK=1``
  (read/write version vectors per view group + the fusion-equivalence
  oracle that replays each flushed segment unfused and bit-compares).
* ``tsan`` — the grafttsan runtime happens-before race detector
  (pass 3, ``GRAFT_TSAN=1``): vector-clock epochs per thread, EH2xx
  reports with both racing stacks for the threaded overlap stack.
* ``lockstep`` — the SPMD lockstep divergence auditor: rolling
  collective-stream hash piggybacked on the dist heartbeat
  (``GRAFT_LOCKSTEP_CHECK``), cross-checked offline by
  ``telemetry/aggregate.py``.
* ``concurrency`` — static GL2xx concurrency lint (pass 4) over the
  package sources, run by the graftlint CLI alongside the op contracts.
* ``compile_safety`` — graftguard (pass 5): GL3xx compile-safety lint
  over trace-eligible closures (host round-trips, traced branching,
  constant-baked hyperparameters, donation hazards).

Kept import-light on purpose: ``engine.py`` imports ``engine_check`` at
module load, long before the ops package exists; ``tsan``/``lockstep``
import telemetry lazily (only when a report fires).
"""

__all__ = ["compile_safety", "concurrency", "contracts", "engine_check",
           "graftlint", "lockstep", "tsan"]
