"""The one input generator: a seeded batch made on the device, in one
jitted call, placed with the sharding the job wants.  A configuration's
JSON says which kind of input its model takes."""


def make_batch(jax, seed, rows, row_shape, sizes, sharding):
    """``(x, y)`` for ``rows`` rows.  Images: uniform [0, 1) float32 and a
    uniform class label.  Tokens: uniform ids as int32 (the fused step keeps
    wide integers exact) and uniform next-token labels.  Labels are float32,
    as the repo's pick-based loss takes them."""
    import jax.numpy as jnp
    spec = sizes["input"]

    def images(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (rows,) + tuple(row_shape), jnp.float32)
        y = jax.random.randint(ky, (rows,), 0, sizes[spec["classes_key"]])
        return x, y.astype(jnp.float32)

    def tokens(key):
        kx, ky = jax.random.split(key)
        vocab = sizes[spec["vocab_key"]]
        x = jax.random.randint(kx, (rows,) + tuple(row_shape), 0, vocab)
        y = jax.random.randint(ky, (rows,) + tuple(row_shape), 0, vocab)
        return x.astype(jnp.int32), y.astype(jnp.float32)

    kinds = {"images": images, "tokens": tokens}
    if spec["kind"] not in kinds:
        raise SystemExit("chip benchmark: unknown input kind %r"
                         % spec["kind"])
    gen = jax.jit(kinds[spec["kind"]], out_shardings=(sharding, sharding))
    return gen(jax.random.key(seed))
