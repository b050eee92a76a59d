"""Driver ``fused``: hand the loop to ``parallel.DataParallelTrainer``.

One jitted, donated XLA program per step (forward, loss, backward, update)
over a ``dp`` mesh of the cell's chips; the batch sits on the device, placed
with the trainer's batch sharding; parameters are replicated and GSPMD puts
the gradient all-reduce into the program.  The repo's fast path.
"""


def setup(run, net, x, y):
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import DataParallelTrainer

    opt = dict(run.sizes["optimizer"])
    dtype = run.traffic["dtype"]
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer=opt.pop("name"),
        optimizer_params=opt, mesh=run.mesh,
        dtype=None if dtype == "float32" else dtype)
    # places parameters and optimizer state on the mesh; the first step()
    # of the warm-up traces and compiles (or reads) the fused program
    trainer.compile(x, y)
    return Job(run, trainer, x, y)


class Job:
    def __init__(self, run, trainer, x, y):
        self.run, self.trainer, self.x, self.y = run, trainer, x, y
        self.loss = None

    def step(self):
        self.loss = self.trainer.step(self.x, self.y)
        return self.loss

    def temp_bytes(self):
        """``temp_size_in_bytes`` of the compiled step: the allocator's
        peak misses a program's temporaries on this runtime (PERF.md,
        PR 21).  ``compiled_step`` lowers from the shapes and shardings of
        the trainer's state, so nothing runs; the program that has just
        run is read back from the compile cache."""
        analysis = self.trainer.compiled_step(self.x,
                                              self.y).memory_analysis()
        return None if analysis is None else int(analysis.temp_size_in_bytes)

    def state(self):
        """(parameters, optimizer state) as the step holds them on the
        device, by parameter name, for ``chipbench/first_steps.py``.  The
        trainer has no accessor for either (PERF.md, open questions)."""
        return self.trainer._params, self.trainer._opt_state

    def checks(self):
        """The loss and every parameter sit on all of the cell's devices."""
        want = set(self.run.devices)
        placed = self.loss.sharding.device_set == want and all(
            v.sharding.device_set == want
            for v in self.trainer._params.values())
        return {"loss_and_parameters_on_every_device": placed}
