"""The first steps of a training job against the plain reference's.

Where a configuration's JSON names a model without randomness
(``first_loss_with``) and an optimizer that has a plain twin here, set-up
drives that model's job, the driver's own, through three steps of the
cell's batch and reads from it

``losses``        the three steps' losses;
``grad_norms``    per parameter, the norm of the first gradient as the
                  optimizer got it, worked out from its state after one
                  step (Adam: the first moment over 1 - beta1);
``change_norms``  per parameter, the norm of its change after two steps.

``reference_side`` reads the same from plain float32 ``jax.numpy``: the
configuration's reference differentiated a block of rows at a time at
``highest`` matmul precision, under the optimizer as its paper states it.
It imports nothing of the program and keeps two gradient trees, no
moments and no second copy of the weights (after two steps Adam's moments
and both updates are functions of the two gradients, and every point it
differentiates at is made from the starting weights, which stay on the
host), so that it fits on a chip the job has left.
Two steps where the job makes three: the third is followed by its loss
alone.  ``compare`` is the worst of each: a loss against the reference's,
a norm by the worst leaf, the gap between the two norms (not the norm of
the difference) over the reference's norm of that leaf or of the median
leaf, whichever is larger, since some gradients are all but zero.  A leaf
whose reference gradient is under ``NO_GRADIENT`` of the median leaf's has
none (OPT's key bias: the softmax does not see it), and its change is left
out: Adam scales whatever rounding puts there up to a full step.

``matmuls_as`` and ``moments_as`` make the reference the control that
``control.py`` reads: the same steps with both operands of every matrix
product, forward and backward, rounded to a lower type; with the moments
kept in one.
"""
import statistics

NO_GRADIENT = 1e-4


def _adam_direction(g1, g2, opt, rnd):
    """Plain Adam's first two updates over the gradients ``g1`` and ``g2``,
    in units of the learning rate (Kingma & Ba, Algorithm 1: bias-corrected
    moments, epsilon beside the corrected root).  ``g2`` None: the first
    alone.  ``rnd`` rounds a moment to the type it is kept in."""
    import jax.numpy as jnp
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    m1, v1 = rnd((1 - b1) * g1), rnd((1 - b2) * g1 * g1)
    u1 = (m1 / (1 - b1)) / (jnp.sqrt(v1 / (1 - b2)) + eps)
    if g2 is None:
        return u1
    m2, v2 = rnd(b1 * m1 + (1 - b1) * g2), rnd(b2 * v1 + (1 - b2) * g2 * g2)
    return (m2 / (1 - b1 ** 2)) / (jnp.sqrt(v2 / (1 - b2 ** 2)) + eps)


# optimizer name -> (the gradient's norm from the state after one step,
#                    the plain updates over one or two gradients)
PLAIN = {
    "adam": (lambda norm, state, opt: norm(state[0]) / (1 - opt["beta1"]),
             _adam_direction),
}


def applies(sizes):
    return bool(sizes.get("first_loss_with")) and (
        sizes["optimizer"]["name"] in PLAIN)


def _rounded(a, dtype):
    """``a`` as ``dtype`` holds it, scaled so that its largest element is
    the type's, and back in float32."""
    import jax.numpy as jnp
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(
        jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(dtype).astype(jnp.float32) / scale


def with_matmuls_as(fn, dtype):
    """``fn`` with both operands of every ``dot_general`` it makes rounded
    to ``dtype``: its jaxpr, the backward pass included where ``fn`` is a
    gradient, evaluated again with that one primitive changed."""
    import jax
    from jax.extend.core import Literal

    def evaluate(jaxpr, consts, *args):
        env = dict(zip(jaxpr.constvars, consts))
        env.update(zip(jaxpr.invars, args))
        for eqn in jaxpr.eqns:
            vals = [v.val if isinstance(v, Literal) else env[v]
                    for v in eqn.invars]
            inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            if eqn.primitive.name == "dot_general":
                out = eqn.primitive.bind(*(_rounded(v, dtype) for v in vals),
                                         **eqn.params)
            elif inner is not None:     # a jitted or custom-rule function
                out = evaluate(inner.jaxpr, inner.consts, *vals)
            else:
                out = eqn.primitive.bind(*vals, **eqn.params)
            env.update(zip(eqn.outvars, out if eqn.primitive.multiple_results
                           or inner is not None else [out]))
        return [v.val if isinstance(v, Literal) else env[v]
                for v in jaxpr.outvars]

    def lowered(*args):
        closed, shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        out = evaluate(closed.jaxpr, closed.consts,
                       *jax.tree.leaves(args))
        return jax.tree.unflatten(jax.tree.structure(shape), out)

    return lowered


def _floats(tree):
    import numpy as np
    return {n: float(np.asarray(v)) for n, v in tree.items()}


def mean(loss):
    import numpy as np
    return float(np.mean(np.asarray(loss)))


def program_side(run, job, first_loss, start):
    """Drive ``job``, which has made one step (loss ``first_loss``), through
    two more.  ``start`` holds the weights it started from, on the host (a
    step donates its arguments, and a chip does not hold a second copy
    beside the job's step), by the names the reference has them under: the
    job's, less the model's prefix."""
    import jax
    import jax.numpy as jnp
    opt = run.sizes["optimizer"]
    from_state, _ = PLAIN[opt["name"]]
    cut = len(run.module.PREFIX)

    def norm(a):
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))

    _, state = job.state()
    grad_norms = _floats(jax.jit(lambda s: {
        n[cut:]: from_state(norm, s[n], opt) for n in s})(state))
    del state
    losses = [first_loss, mean(job.step())]
    params, state = job.state()
    before = jax.device_put({n: start[n[cut:]] for n in state},
                            run.devices[0])
    change_norms = _floats(jax.jit(lambda a, b: {
        n[cut:]: norm(a[n] - b[n]) for n in b})(params, before))
    del before, params, state
    losses.append(mean(job.step()))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


def reference_side(jax, module, sizes, start, x, y, rows, device,
                   matmuls_as=None, moments_as=None):
    """The same three readings from the plain reference.  ``start`` maps a
    parameter's name to its float32 weights on the host; ``rows`` is how
    many rows of the batch are differentiated at a time."""
    import jax.numpy as jnp
    opt = sizes["optimizer"]
    _, direction = PLAIN[opt["name"]]
    lr = opt["learning_rate"]
    blocks = [(jax.device_put(x[i:i + rows], device),
               jax.device_put(y[i:i + rows], device))
              for i in range(0, x.shape[0], rows)]

    def rnd(a):
        return a if moments_as is None else a.astype(moments_as).astype(
            jnp.float32)

    def loss_of(p, a, b):
        return module.reference_loss(module.reference(sizes, p, a), b)

    loss_and_gradient = jax.value_and_grad(loss_of)
    if matmuls_as is not None:
        loss_of, loss_and_gradient = (with_matmuls_as(f, matmuls_as)
                                      for f in (loss_of, loss_and_gradient))

    def norm(a):
        return jnp.sqrt(jnp.sum(jnp.square(a)))

    def moved(g1, g2):
        """The two updates together, in units of the learning rate."""
        return direction(g1, None, opt, rnd) + direction(g1, g2, opt, rnd)

    def point(step, *grads):
        """The weights a step starts from: made from the starting weights
        and the gradients so far."""
        return jax.jit(lambda w, *g: jax.tree.map(
            lambda w, *g: w - lr * step(*g), w, *g),
            donate_argnums=0)(jax.device_put(start, device), *grads)

    with jax.default_matmul_precision("highest"):
        add = jax.jit(lambda acc, p, a, b: jax.tree.map(
            jnp.add, acc, loss_and_gradient(p, a, b)),
            donate_argnums=0)
        forward = jax.jit(loss_of)
        over_blocks = jax.jit(lambda t: jax.tree.map(
            lambda v: v / len(blocks), t), donate_argnums=0)

        def gradient(p):
            acc = (jnp.zeros((), jnp.float32),
                   jax.tree.map(jnp.zeros_like, p))
            for a, b in blocks:
                acc = add(acc, p, a, b)
            return over_blocks(acc)

        loss1, g1 = gradient(point(lambda: 0.0))
        grad_norms = _floats(jax.jit(
            lambda g: jax.tree.map(norm, g))(g1))
        loss2, g2 = gradient(point(
            lambda a: direction(a, None, opt, rnd), g1))
        change_norms = _floats(jax.jit(lambda a, b: jax.tree.map(
            lambda u, v: lr * norm(moved(u, v)), a, b))(g1, g2))
        p = point(moved, g1, g2)
        del g1, g2
        loss3 = sum(forward(p, a, b) for a, b in blocks) / len(blocks)
    return {"losses": [float(loss1), float(loss2), float(loss3)],
            "grad_norms": grad_norms, "change_norms": change_norms}


def compare(got, want):
    """``(gaps, where)``: the worst of each reading of ``got`` against
    ``want``'s, and the leaf (or step) it was read at."""
    def worst_leaf(a, b, leaves):
        floor = statistics.median(b.values())
        return max((abs(a[n] - b[n]) / max(b[n], floor), n) for n in leaves)

    grads = want["grad_norms"]
    least = NO_GRADIENT * statistics.median(grads.values())
    found = {
        "steps_loss": max((abs(a - b) / b, "step %d" % i) for i, (a, b) in
                          enumerate(zip(got["losses"], want["losses"]), 1)),
        "first_grad_norm": worst_leaf(got["grad_norms"], grads, grads),
        "param_change_norm": worst_leaf(
            got["change_norms"], want["change_norms"],
            [n for n in grads if grads[n] >= least]),
    }
    return ({k: v[0] for k, v in found.items()},
            {k: v[1] for k, v in found.items()})
