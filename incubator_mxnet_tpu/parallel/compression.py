"""Wire-level 2-bit gradient packing.

The reference compresses gradients to 2 bits per value and packs 16
values into each 32-bit word before they touch the network
(src/kvstore/gradient_compression.h:37-132, quantize_2bit in the .cu
twin: code 0 = zero, 1 = +threshold, 2 = -threshold).  Round 2 carried
the *algebra* (quantize + residual) but shipped full f32 words — zero
bandwidth saved.  This module supplies the missing wire format as XLA
kernels:

* ``encode_2bit``     — {-t, 0, +t} values → packed uint32 (16 lanes/word)
* ``decode_2bit_sum`` — (num_workers, nwords) packed → f32 sum over workers

and the collective that moves ONLY packed words between processes:
``allgather_packed`` is a jitted identity whose input is sharded over the
one-device-per-process "worker" mesh and whose output is replicated — XLA
lowers exactly one all-gather of the uint32 payload (1/16 the bytes of
the f32 buffer).  Dequantize + sum then run as local, comm-free XLA ops
on every worker — each worker plays the reference server's dequant role
(kvstore_dist_server.h:389 DataHandleCompressed), collapsed into the
allreduce topology the TPU wire actually has.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["encode_2bit", "decode_2bit", "decode_2bit_sum",
           "allgather_packed", "packed_nbytes", "allreduce_packed_sum",
           "wire_bytes_per_worker"]

_LANES = 16  # 2-bit codes per uint32 word (gradient_compression.h:44)


def packed_words(n):
    return (n + _LANES - 1) // _LANES


def packed_nbytes(n):
    """Bytes on the wire for n values — the 1/16-of-f32 contract."""
    return 4 * packed_words(n)


@jax.jit
def _encode(q, half_t):
    n = q.shape[0]
    nw = packed_words(n)
    codes = jnp.where(q > half_t, jnp.uint32(1),
                      jnp.where(q < -half_t, jnp.uint32(2), jnp.uint32(0)))
    codes = jnp.pad(codes, (0, nw * _LANES - n))
    shifts = (jnp.arange(_LANES, dtype=jnp.uint32) * 2)[None, :]
    # disjoint bit fields: the sum IS the bitwise-or of the shifted lanes
    return jnp.sum(codes.reshape(nw, _LANES) << shifts, axis=1,
                   dtype=jnp.uint32)


def encode_2bit(q, threshold):
    """Pack a flat f32 buffer of quantized values {-t, 0, +t} into uint32
    words, 16 two-bit codes per word."""
    return _encode(q.ravel(), jnp.float32(threshold / 2.0))


def _lanes(words):
    shifts = (jnp.arange(_LANES, dtype=jnp.uint32) * 2)
    return (words[..., None] >> shifts[None, :]) & jnp.uint32(3)


@jax.jit
def _decode(words, t):
    c = _lanes(words)
    vals = jnp.where(c == 1, t, jnp.where(c == 2, -t, jnp.float32(0.0)))
    return vals.reshape(words.shape[:-1] + (-1,))


def decode_2bit(words, threshold, n):
    """Unpack one worker's words back to the quantized f32 values."""
    return _decode(words, jnp.float32(threshold))[..., :n]


@jax.jit
def _decode_sum(words_all, t):
    c = _lanes(words_all)  # (W, nw, LANES)
    vals = jnp.where(c == 1, t, jnp.where(c == 2, -t, jnp.float32(0.0)))
    return jnp.sum(vals, axis=0).reshape(-1)


def decode_2bit_sum(words_all, threshold, n):
    """(num_workers, nwords) packed → f32[n] sum of all workers' values.
    Pure local compute (the per-worker 'server-side' dequant+merge)."""
    return _decode_sum(words_all, jnp.float32(threshold))[:n]


def _assemble_worker_global(local, mesh):
    """Build the (W, ...) global array whose row for THIS process is
    ``local``, sharded over the mesh's 'worker' axis (one device per
    process — the kvstore wire topology)."""
    me = jax.process_index()
    my_dev = next(d for d in mesh.devices.flat if d.process_index == me)
    piece = jax.device_put(local[None], my_dev)
    return jax.make_array_from_single_device_arrays(
        (mesh.shape["worker"],) + tuple(local.shape),
        NamedSharding(mesh, P("worker")), [piece])


def _sum_code_dtype(W):
    # shard sums are exact integer multiples of t in [-W, W]
    return jnp.int8 if W <= 127 else jnp.int16


def wire_bytes_per_worker(n, W):
    """(compressed, dense) bytes a worker RECEIVES for an n-value reduce.

    Compressed = packed all-to-all (2-bit codes) + int8 sum all-gather —
    both W-independent (~n/4 + n); dense = ring all-reduce of f32
    (~8n).  The old allgather-of-codes wire was (W-1)·n/4 — worse than
    dense past W≈33 and O(W·n) decode; this one wins at every W.
    """
    nw = packed_words(n)
    k = -(-nw // W)
    code_bytes = 1 if W <= 127 else 2
    compressed = (W - 1) * k * 4 + (W - 1) * k * _LANES * code_bytes
    dense = 2 * 4 * n * (W - 1) // W
    return compressed, dense


_rs_jit_cache = {}


def _rs_jitted(mesh, W, k, sum_dtype):
    """Jit: (W, W·k) packed words sharded over 'worker' → replicated
    (W·k·16,) integer sum codes.  Per shard-map block: all_to_all ships
    each destination its k-word slice from every worker (the compressed
    reduce-scatter), the block decodes ONLY its shard (O(n/W) lanes) and
    sums over workers; the replicated out_sharding makes GSPMD all-gather
    the narrow integer codes, not f32."""
    key = (mesh, W, k, sum_dtype)
    fn = _rs_jit_cache.get(key)
    if fn is None:
        from jax import lax, shard_map

        def body(block):                       # (1, W*k) uint32
            shards = block[0].reshape(W, k)    # row j → destination j
            recv = lax.all_to_all(shards, "worker", split_axis=0,
                                  concat_axis=0, tiled=False)
            recv = recv.reshape(W, k)          # row j → worker j's slice
            c = _lanes(recv)                   # (W, k, 16)
            vals = jnp.where(c == 1, 1, jnp.where(c == 2, -1, 0))
            return vals.sum(axis=0, dtype=jnp.int32).astype(
                sum_dtype).reshape(1, -1)      # (1, k*16)

        def run(garr):
            out = shard_map(body, mesh=mesh,
                            in_specs=P("worker", None),
                            out_specs=P("worker", None),
                            check_vma=False)(garr)
            return out.reshape(-1)

        fn = jax.jit(run, out_shardings=NamedSharding(mesh, P()))
        _rs_jit_cache[key] = fn
    return fn


def allreduce_packed_sum(words, threshold, n, mesh):
    """Scale-correct compressed all-reduce: this process's packed words in,
    replicated f32[n] sum of every worker's values out.

    Wire cost per worker is W-independent (see wire_bytes_per_worker);
    decode compute is O(n) total per worker (each decodes only its own
    shard of every peer).  The int8 re-encode of the shard sums is EXACT:
    sums are integer multiples of the threshold with |multiple| ≤ W
    (int16 beyond 127 workers).  ref: gradient_compression.h:37-132 wire
    format; kvstore_dist_server.h:389 server-side dequant role, here
    distributed across the reduce-scatter shards."""
    W = mesh.shape["worker"]
    nw = words.shape[0]
    k = -(-nw // W)
    wordsp = jnp.pad(words, (0, k * W - nw))
    sum_dtype = _sum_code_dtype(W)
    fn = _rs_jitted(mesh, W, k, sum_dtype)
    garr = _assemble_worker_global(wordsp, mesh)
    codes = jnp.asarray(fn(garr).addressable_data(0))
    return codes[:n].astype(jnp.float32) * jnp.float32(threshold)


_gather_jit_cache = {}


def allgather_packed(words, mesh):
    """Ship THIS process's packed words to every process; returns the
    replicated (num_workers, nwords) uint32 array.  The only bytes that
    cross the wire are the packed codes."""
    _gather_jit = _gather_jit_cache.get(mesh)
    if _gather_jit is None:
        _gather_jit = jax.jit(lambda a: a,
                              out_shardings=NamedSharding(mesh, P()))
        _gather_jit_cache[mesh] = _gather_jit
    out = _gather_jit(_assemble_worker_global(words, mesh))
    return jnp.asarray(out.addressable_data(0))
