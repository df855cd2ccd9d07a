"""Gradient compression for the cross-pod data-parallel all-reduce.

At 1000+ nodes the inter-pod links (DCN) are the scarcest bandwidth; the
standard trick is a two-phase compressed all-reduce with **error feedback**:

    1. reduce-scatter the int8-quantized gradient chunks (all_to_all + local sum)
    2. all-gather the int8-quantized reduced chunks
    3. feed the quantization residual back into the next step's gradient

Wire bytes drop 4× vs f32 (2× vs bf16); error feedback makes the scheme
convergent (Karimireddy et al., 2019).  The collectives are expressed with
``jax.lax`` primitives inside ``shard_map`` so the HLO shows real
all-to-all / all-gather ops on the pod axis.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


def _quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_all_reduce_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Int8 two-phase all-reduce along ``axis_name`` (call inside shard_map).

    x: any shape; flattened internally; returns mean over the axis."""
    n = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)

    # phase 1: quantize my n chunks, all_to_all so peer i gets chunk i from
    # everyone, dequantize + sum → I own the reduced chunk i.
    q, scale = _quantize(chunks)
    q_t = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, axis_name)
    mine = jnp.sum(q_t.astype(jnp.float32) * scales[:, None], axis=0)

    # phase 2: quantize the reduced chunk, all-gather.
    q2, scale2 = _quantize(mine)
    qs = jax.lax.all_gather(q2, axis_name)
    s2 = jax.lax.all_gather(scale2, axis_name)
    out = (qs.astype(jnp.float32) * s2[:, None]).reshape(-1)
    out = out[: x.size] / n
    return out.reshape(x.shape).astype(x.dtype)


def ef_compressed_all_reduce_mean(x: jax.Array, err: jax.Array, axis_name: str):
    """Error-feedback wrapper: returns (reduced, new_error)."""
    corrected = x.astype(jnp.float32) + err.astype(jnp.float32)
    reduced = compressed_all_reduce_mean(corrected, axis_name)
    # residual of *this device's* contribution
    q, scale = _quantize(corrected.reshape(-1))
    approx = _dequantize(q, scale).reshape(x.shape)
    new_err = corrected - approx
    return reduced.astype(x.dtype), new_err.astype(err.dtype)


def make_compressed_grad_reducer(mesh, axis_name: str = "pod"):
    """Tree-level reducer over the pod axis via shard_map.

    grads must be pod-local (i.e. produced inside an outer shard_map over the
    pod axis, or with batch sharded only over 'data').  Returns
    (reduce_fn, init_err_fn)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def reduce_tree(grads, errs):
        def per_leaf(g, e):
            fn = shard_map(partial(ef_compressed_all_reduce_mean, axis_name=axis_name),
                           mesh=mesh,
                           in_specs=(P(), P()), out_specs=(P(), P()),
                           check_vma=False)
            return fn(g, e)

        flat_g, tdef = jax.tree_util.tree_flatten(grads)
        flat_e = tdef.flatten_up_to(errs)
        out = [per_leaf(g, e) for g, e in zip(flat_g, flat_e)]
        return (tdef.unflatten([o[0] for o in out]),
                tdef.unflatten([o[1] for o in out]))

    def init_err(grads):
        return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    return reduce_tree, init_err
