"""Device-mesh parallel plane: ICI-collective shuffles and distributed
relational steps.

Where the reference shuffles through per-machine Arrow Flight servers over the
network (pyquokka/flight.py + core.py:324-371), quokka-tpu adds a second, much
faster path for device-resident data inside a pod slice: hash-partition rows
on-device and exchange them with a single XLA all_to_all over ICI, inside one
jitted shard_map program.  The host data plane remains for cross-slice / DCN
movement; this module is the intra-slice fast path and the multi-chip execution
model (channels == mesh shards — the reference's channel data-parallelism
mapped onto jax.sharding).

Everything here is static-shape: each device owns N local (padded) rows; a
shuffle exchanges P buckets of capacity C = N (a bucket from one device can
never exceed its local rows), so the program compiles once per (N, P, schema).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from quokka_tpu import config
from quokka_tpu.analysis import compat


def make_mesh(n_devices: Optional[int] = None, axis: str = "dp") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """Every mesh program goes through this one spelling of
    ``jax.shard_map`` (replication checking off by default: the steps mix
    per-shard and replicated values freely)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# ---------------------------------------------------------------------------
# collective hash shuffle (the ICI fast path)
# ---------------------------------------------------------------------------


def _hash_u32(limbs: Sequence[jax.Array]) -> jax.Array:
    h = jnp.zeros(limbs[0].shape[0], dtype=jnp.uint32)
    for limb in limbs:
        u = limb.astype(jnp.int32).astype(jnp.uint32)
        h = h * jnp.uint32(0x9E3779B1) + u
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
    return h


def _local_bucketize(cols: Tuple[jax.Array, ...], valid, key_idx, n_parts):
    """Sort local rows into P contiguous buckets of capacity N (static)."""
    n = valid.shape[0]
    limbs = [cols[i] for i in key_idx]
    pid = (_hash_u32(limbs) % jnp.uint32(n_parts)).astype(jnp.int32)
    pid = jnp.where(valid, pid, n_parts)  # invalid rows sort last
    iota = jnp.arange(n, dtype=jnp.int32)
    sorted_ops = lax.sort([pid, iota], num_keys=1)
    perm = sorted_ops[1]
    pid_sorted = sorted_ops[0]
    # position of each row within its bucket
    counts = jax.ops.segment_sum(
        jnp.ones(n, jnp.int32), pid_sorted, num_segments=n_parts + 1
    )
    starts = jnp.cumsum(counts) - counts
    pos_in_bucket = iota - starts[pid_sorted]
    # scatter rows into [P, N] frames; invalid rows carry pid == n_parts which
    # is out of bounds and dropped (mode="drop") rather than clipped into the
    # last real partition
    frame_valid = jnp.zeros((n_parts, n), dtype=bool)
    frame_valid = frame_valid.at[pid_sorted, pos_in_bucket].set(True, mode="drop")
    out_cols = []
    for c in cols:
        cs = c[perm]
        frame = jnp.zeros((n_parts, n), dtype=c.dtype)
        frame = frame.at[pid_sorted, pos_in_bucket].set(cs, mode="drop")
        out_cols.append(frame)
    return tuple(out_cols), frame_valid


def collective_hash_shuffle(
    cols: Tuple[jax.Array, ...],
    valid: jax.Array,
    key_idx: Tuple[int, ...],
    axis: str = "dp",
):
    """Inside shard_map: redistribute rows so equal-key rows land on the same
    device.  Input: per-device local columns [N]; output: [P*N] padded local
    columns after an all_to_all over the mesh axis."""
    n_parts = compat.axis_size(axis)
    frames, frame_valid = _local_bucketize(cols, valid, key_idx, n_parts)
    out_cols = []
    for f in frames:
        got = lax.all_to_all(f, axis, split_axis=0, concat_axis=0, tiled=False)
        out_cols.append(got.reshape(-1))
    got_valid = lax.all_to_all(frame_valid, axis, split_axis=0, concat_axis=0)
    return tuple(out_cols), got_valid.reshape(-1)


# ---------------------------------------------------------------------------
# distributed relational steps (jit-able whole programs over a Mesh)
# ---------------------------------------------------------------------------


def distributed_groupby_step(
    mesh: Mesh,
    key_cols: int,
    val_ops: Tuple[str, ...],
    axis: str = "dp",
):
    """Jitted distributed group-by-aggregate: local partial agg -> all_to_all
    shuffle of partials by key hash -> final agg per device.  Built from the
    SAME kernel the embedded engine uses (ops/kernels.sorted_groupby) — the
    full-plan version of this (with carried key values, AggPlan decomposition,
    string keys) lives in parallel/mesh_exec.mesh_groupby, which is what
    QuokkaContext(mesh=...) executes."""
    from quokka_tpu.ops import kernels

    recombine = tuple("sum" if op == "count" else op for op in val_ops)

    def _grouped(keys, vals, ops, valid):
        n = valid.shape[0]
        outs, _, rep, num = kernels.sorted_groupby(tuple(keys), tuple(vals), ops, valid)
        gkeys = tuple(k[rep] for k in keys)
        return gkeys, tuple(outs), jnp.arange(n) < num

    def step(*arrays):
        keys = arrays[:key_cols]
        vals = arrays[key_cols : key_cols + len(val_ops)]
        valid = arrays[-1]
        gkeys, gvals, gvalid = _grouped(keys, vals, val_ops, valid)
        cols = tuple(gkeys) + tuple(gvals)
        key_idx = tuple(range(key_cols))
        shuf, shuf_valid = collective_hash_shuffle(cols, gvalid, key_idx, axis)
        fkeys, fvals, fvalid = _grouped(
            shuf[:key_cols], shuf[key_cols:], recombine, shuf_valid
        )
        return fkeys + fvals + (fvalid,)

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(sharded)


def distributed_join_groupby_step(mesh: Mesh, axis: str = "dp"):
    """Distributed shuffle-join + psum reduction built from the engine's rank
    join kernel (ops/join._pk_match): two dp-sharded tables are key-shuffled
    (all_to_all), PK-joined per device, and the joined product is psum-reduced
    to a replicated scalar.  Full relational joins over a mesh run through
    parallel/mesh_exec.mesh_join."""
    from quokka_tpu.ops import join as join_ops

    def step(l_key, l_val, l_valid, r_key, r_val, r_valid):
        (lk, lv), lvalid = collective_hash_shuffle((l_key, l_val), l_valid, (0,), axis)
        (rk, rv), rvalid = collective_hash_shuffle((r_key, r_val), r_valid, (0,), axis)
        p = lk.shape[0]
        limbs = (jnp.concatenate([lk, rk.astype(lk.dtype)]),)
        valid = jnp.concatenate([lvalid, rvalid])
        build_idx, matched = join_ops._pk_match(limbs, valid, p)
        rv_matched = rv[build_idx]
        prod = jnp.where(matched, lv * rv_matched, 0.0)
        total = lax.psum(jnp.sum(prod), axis)
        rows = lax.psum(jnp.sum(matched.astype(jnp.int32)), axis)
        return total, rows

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)
