"""Distributed-optimization tricks: int8-compressed gradient ring
reduce-scatter + all-gather (bandwidth ~4x lower than fp32 all-reduce),
built from shard_map + ppermute.

Quantization: per-chunk absmax scaling to int8; the ring accumulates in
fp32 locally and re-quantizes per hop (error stays bounded by 1/127 per
hop; tests check end-to-end relative error).  Used by the train driver
when --grad-compress is set; the default path relies on GSPMD's implicit
fp32 all-reduce."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _quant(x):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequant(q, scale):
    return q.astype(jnp.float32) * scale


def ring_reduce_scatter_q8(x, axis_name: str):
    """x: (n_shards * chunk,) fp32 per device -> (chunk,) = fully-reduced
    chunk `me`.  The partial sum for chunk c starts at device (c+1)%n and
    rings to c, each hop quantized to int8 + one fp32 scale."""
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    xs = x.reshape(n, -1)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, acc):
        q, s = _quant(acc)
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        c = (me - 2 - t) % n          # chunk id of the partial just received
        mine = jax.lax.dynamic_index_in_dim(xs, c, 0, keepdims=False)
        return _dequant(q, s) + mine

    acc0 = jax.lax.dynamic_index_in_dim(xs, (me - 1) % n, 0, keepdims=False)
    return jax.lax.fori_loop(0, n - 1, body, acc0)


def compressed_allreduce(x, axis_name: str):
    """reduce-scatter (int8 ring) + int8 all-gather: psum replacement at
    ~1/4 the wire bytes."""
    n = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad))
    shard = ring_reduce_scatter_q8(flat, axis_name)
    q, s = _quant(shard)
    qg = jax.lax.all_gather(q, axis_name)            # (n, chunk)
    sg = jax.lax.all_gather(s, axis_name)            # (n, 1)
    full = _dequant(qg, sg).reshape(-1)
    return full[:x.size].reshape(x.shape)


def make_compressed_grad_sync(mesh, axis_name="data"):
    """shard_map wrapper syncing a grad pytree across the data axis with
    int8 ring collectives (grads enter replicated per data-shard)."""
    from jax.sharding import PartitionSpec as P

    def sync(grads):
        def inner(g):
            return jax.tree_util.tree_map(
                lambda a: compressed_allreduce(a, axis_name) /
                jax.lax.axis_size(axis_name), g)
        from ..launch.mesh import shard_map
        return shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                         check=False)(grads)

    return sync
