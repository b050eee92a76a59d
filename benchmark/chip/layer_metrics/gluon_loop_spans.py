"""The Gluon loop's three calls, on the host clock: the benchmark's own
spans around ``net(x)`` + loss under ``autograd.record()``, around
``loss.backward()`` and around ``Trainer.step``.  Present only where the
driver records them."""
from chipbench import timing

_SPANS = {"fwd_host_ms_p50": "forward", "bwd_host_ms_p50": "backward",
          "update_host_ms_p50": "update"}


def read(run):
    return {metric: 1e3 * timing.percentile(run.spans[span], 50)
            for metric, span in _SPANS.items() if run.spans.get(span)}
