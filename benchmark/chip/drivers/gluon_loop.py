"""Driver ``gluon_loop``: the MXNet training loop as a user writes it.

``net.hybridize()``, then per step ``autograd.record()`` around the forward
and the loss, ``loss.backward()``, ``gluon.Trainer.step(batch)``: the loop of
``example/image-classification/common.py::fit_gluon``, with every switch at
its default.  ``parallel.DataParallelTrainer`` is bypassed entirely; the
host's work is done by ``CachedOp``, ``autograd``, ``engine.py``,
``gluon/trainer.py``'s bucketed update and the always-on observation.
"""


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
        """This path has no single program whose temporaries can be read
        through a public entry point, so the cell reports no
        ``peak_hbm_gb``.  The largest, the CachedOp's compiled backward
        (which recomputes the forward), is read from the Block's private
        cache all the same, for ``device.memory_peak_bytes`` alone: the
        allocator's own peak misses a program's temporaries (2.8 GB against
        9.4 GB in this cell, PERF.md), and a cell is judged too small or not
        by that number.  None when the cache is not where it was."""
        import jax
        try:
            (entry,) = self.net._cached_op._cache.values()
            names = self.net._cached_op._param_names
            params = self.net.collect_params()
            vals = {n: params[n].data()._read() for n in names}
            out = jax.eval_shape(entry["jit"], vals, [self.x._read()],
                                 jax.random.PRNGKey(0))[0]
            analysis = entry["vjp"].lower(
                vals, [self.x._read()], jax.random.PRNGKey(0),
                tuple(out)).compile().memory_analysis()
            return int(analysis.temp_size_in_bytes)
        except (AttributeError, KeyError, TypeError, ValueError) as e:
            self.run.facts["gluon_temp_bytes_unreadable"] = repr(e)
            return None

    def checks(self):
        return {}
