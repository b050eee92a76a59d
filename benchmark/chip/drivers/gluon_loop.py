"""Driver ``gluon_loop``: the MXNet training loop as a user writes it.

``net.hybridize()``, then per step ``autograd.record()`` around the forward
and the loss, ``loss.backward()``, ``gluon.Trainer.step(batch)``: the loop of
``example/image-classification/common.py::fit_gluon``, with every switch at
its default.  ``parallel.DataParallelTrainer`` is bypassed entirely; the
host's work is done by ``CachedOp``, ``autograd``, ``engine.py``,
``gluon/trainer.py``'s bucketed update and the always-on observation.
"""

BACKWARD = "cachedop_backward"      # gluon/block.py's name for it


def setup(run, net, x, y):
    from incubator_mxnet_tpu import gluon

    if run.traffic["dtype"] != "float32" or run.chips != 1:
        raise SystemExit("driver gluon_loop runs float32 on one chip")
    opt = dict(run.sizes["optimizer"])
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), opt.pop("name"), opt,
                            kvstore="device")
    return Job(run, net, trainer, gluon.loss.SoftmaxCrossEntropyLoss(),
               run.mx.nd.NDArray(x), run.mx.nd.NDArray(y))


class Job:
    def __init__(self, run, net, trainer, loss_fn, x, y):
        self.run, self.net, self.trainer = run, net, trainer
        self.loss_fn, self.x, self.y = loss_fn, x, y

    def step(self):
        """One iteration; returns the per-sample losses as a device array
        (the harness takes their mean after the window: a user's loop does
        not fetch the loss every step either)."""
        run, autograd = self.run, self.run.mx.autograd
        with run.span("forward"):
            with autograd.record():
                loss = self.loss_fn(self.net(self.x), self.y)
        with run.span("backward"):
            loss.backward()
        with run.span("update"):
            self.trainer.step(self.x.shape[0])
        return loss._read()

    def temp_bytes(self):
        """The temporaries of the loop's largest program, the CachedOp's
        compiled backward (it recomputes the forward), as the program's own
        registry has them (``telemetry.programs()``: compiled from the
        shapes it last ran on, a read of the compile cache).  The
        allocator's own peak misses a program's temporaries (2.8 GB against
        9.4 GB in this cell, PERF.md), and a cell is judged too small or
        not by this number (``device.memory_peak_bytes``).  The cell lists
        itself under no ``peak_hbm_gb``: the loop's ``bytes_in_use`` swings
        by 2 to 3 % from run to run (PERF.md, PR 25).  None where the
        registry has no such program or no memory analysis of it."""
        program = self.run.mx.telemetry.programs().get(BACKWARD)
        temp = program.memory.get("temp_bytes") if program else None
        if temp is None:
            self.run.facts["gluon_temp_bytes_unreadable"] = (
                program.error if program else "no program %s" % BACKWARD)
            return None
        return int(temp)

    def checks(self):
        return {}
