"""How often the compute dtype chooses other experts than float32.

Top-k is discontinuous: a token whose fourth and fifth selection scores lie
closer than bf16's rounding of the router's input is routed to another
expert in the timed step than in the float32 reference, and its loss moves
with it.  The step's loss and first steps are held to the reference by
limits sized over such tokens (``configs/lfm2_moe.py::TOLERANCE``); this
reader says how many there are.  No metric: ``facts["moe_routing"]``.

After the window, in a traced run, the configuration's model is built once
more from the run's seed and run eagerly over the batch's first row, in
float32 and then cast to the traffic's dtype; each routed layer keeps the
experts it chose on an eager call (``ExpertParallelMoE.last_chosen``).  A
token counts where its set of chosen experts differs in any routed layer,
and ``held`` where an expert of the share is in the difference (only those
move the output here).  Whatever goes wrong is written down and raised no
further: a fact, not a check.
"""
import gc

from chipbench import inputs


def chosen_sets(net):
    """(tokens, experts) 0/1 per routed layer of ``net`` after an eager
    call, and the (first, count) each holds."""
    import jax
    found, stack = [], [net]
    while stack:
        block = stack.pop()
        stack.extend(block._children)
        chosen = getattr(block, "last_chosen", None)
        if chosen is not None:
            sets = jax.nn.one_hot(chosen, block.num_experts).sum(1)
            found.append((block.name, sets, block.experts_held))
    return sorted(found)


def disagreement(run):
    import jax.numpy as jnp
    import numpy as np
    mx, module, sizes = run.mx, run.module, run.sizes
    mx.random.seed(run.args.seed)
    net = module.build(sizes)
    net.initialize(module.initializer(sizes))
    from jax.sharding import NamedSharding, PartitionSpec
    x, _ = inputs.make_batch(         # the run's own batch, from its seed
        run.jax, run.args.seed, run.traffic["batch_per_chip"] * run.chips,
        module.sample_shape(sizes, run.traffic), sizes,
        NamedSharding(run.mesh, PartitionSpec("dp")))
    row = mx.nd.NDArray(run.jax.device_put(np.asarray(x[:1]),
                                           run.devices[0]))
    with mx.autograd.train_mode():
        net(row)
        exact = chosen_sets(net)
        net.cast(run.traffic["dtype"])
        net(row)
        rounded = chosen_sets(net)
    if not exact:
        return None
    tokens = exact[0][1].shape[0]
    differs = jnp.zeros((tokens,), bool)
    on_share = jnp.zeros((tokens,), bool)
    by_layer = {}
    for (name, a, (first, count)), (_, b, _) in zip(exact, rounded):
        changed = a != b                                # (tokens, experts)
        differs |= changed.any(-1)
        on_share |= changed[:, first:first + count].any(-1)
        by_layer[name] = int(np.asarray(changed.any(-1).sum()))
    return {"tokens": int(tokens), "layers": len(exact),
            "tokens_routed_differently": int(np.asarray(differs.sum())),
            "of_them_on_a_held_expert": int(np.asarray(on_share.sum())),
            "by_layer": by_layer, "dtype": run.traffic["dtype"]}


def read(run):
    if "num_experts_published" not in run.sizes or not getattr(
            getattr(run, "args", None), "trace", 0):
        return {}
    try:
        found = disagreement(run)
    except Exception as e:      # noqa: BLE001 - a fact, never a failed run
        found = {"error": "%s: %s" % (type(e).__name__, str(e)[:200])}
    gc.collect()                # the second model is a cycle of Blocks
    if found is not None:
        run.facts["moe_routing"] = found
    return {}
