"""Multi-chip sharding of the crypto plane.

The reference has no collectives (its 'distributed backend' is the TCP
overlay between validators — SURVEY.md §2.9); chips within one validator
host are the new, TPU-idiomatic parallel axis. The batch dimension of the
verify/hash kernels shards data-parallel over ICI via a 1-D
``jax.sharding.Mesh``; cross-chip aggregation (e.g. "did every signature
in the consensus set verify") is an ICI collective (psum), not host code.

Validator-to-validator traffic stays on the overlay (DCN/TCP): the mesh is
intra-node only, matching SURVEY.md §5's "overlay inter-node, ICI
intra-node" design.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.ed25519_jax import verify_kernel
from ..ops.sha512_jax import sha512_blocks

BATCH_AXIS = "batch"


def make_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (BATCH_AXIS,))


def _batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(BATCH_AXIS))


def sharded_verify_kernel(mesh: Mesh):
    """jit of the batched Ed25519 verify with the batch dim sharded over the
    mesh. XLA partitions the whole point-arithmetic pipeline; no host-side
    scatter/gather is involved beyond the initial device_put."""
    shard = _batch_sharding(mesh)
    return jax.jit(
        verify_kernel,
        in_shardings=(shard, shard, shard, shard, shard),
        out_shardings=shard,
    )


def sharded_verify_kernel_pallas(mesh: Mesh):
    """shard_map of the Pallas whole-verify-in-VMEM kernel: each chip
    runs the grid over its local batch shard (a pallas_call is a custom
    call XLA cannot auto-partition, so the data-parallel split is
    explicit shard_map, unlike sharded_verify_kernel's jit+shardings).
    Public layout identical to verify_kernel's; each shard pads itself
    to its block multiple internally."""
    from ..ops.ed25519_pallas import verify_kernel_pallas

    pspec = P(BATCH_AXIS)
    return jax.jit(
        jax.shard_map(
            verify_kernel_pallas,
            mesh=mesh,
            in_specs=(pspec,) * 5,
            out_specs=pspec,
            # a pallas_call's out_shape carries no varying-mesh-axes
            # annotation, so the vma consistency check cannot apply
            check_vma=False,
        )
    )


def sharded_sha512_blocks(mesh: Mesh):
    shard = _batch_sharding(mesh)
    return jax.jit(sha512_blocks, in_shardings=(shard,), out_shardings=shard)


def sharded_masked_sha512(mesh: Mesh):
    """jit of the masked mixed-block-count SHA-512 kernel (the tree
    hasher's leaf/flat-batch workhorse) with the row dim sharded over the
    mesh — the hashing twin of sharded_verify_kernel."""
    from ..ops.treehash_jax import sha512_blocks_masked

    shard = _batch_sharding(mesh)
    return jax.jit(
        sha512_blocks_masked, in_shardings=(shard, shard), out_shardings=shard
    )


def sharded_path_quality(mesh: Mesh):
    """jit of the Q16.16 path-quality fold with the candidate batch dim
    sharded over the mesh — the liquidity plane's flat kernel arm,
    shaped exactly like sharded_masked_sha512 (callers pad the batch to
    a width multiple before dispatch)."""
    from ..ops.pathq_jax import path_quality_kernel

    shard = _batch_sharding(mesh)
    return jax.jit(
        path_quality_kernel, in_shardings=(shard,), out_shardings=shard
    )


def sharded_tree_kernels(mesh: Mesh):
    """-> (leaf_kernel, inner_kernel): the fused close's level-chained
    tree-hash programs, sharded over the mesh with the digest buffer
    DONATED so the whole chain stays device-resident at any width.

    The digest buffer rides every level replicated and is re-donated
    call to call (``donate_argnums=0`` — the pjit idiom from the
    SNIPPETS exemplars): XLA reuses the same device allocation across
    the chain instead of materializing a fresh buffer per level, and
    the host reads it back ONCE after the last level. Leaf batches and
    the assembled inner payloads shard row-wise (every row count is a
    power of two >= 8, so any width up to 8 divides them); the inner
    scatter assembles replicated, then ``with_sharding_constraint``
    splits the 5-block compression — the expensive part — across the
    mesh. Width 1 is a one-device mesh of the SAME programs, not a
    separate code path."""
    from ..ops.treehash_jax import INNER_BLOCKS, tree_leaf_body

    shard = _batch_sharding(mesh)
    rep = NamedSharding(mesh, P())

    leaf = jax.jit(
        tree_leaf_body,
        in_shardings=(rep, shard, shard, None),
        out_shardings=rep,
        donate_argnums=0,
    )

    def inner_body(buf, template, rows, col_base, src_rows, offset):
        vals = buf[src_rows]  # [K, 8]
        cols = col_base[:, None] + jnp.arange(8, dtype=col_base.dtype)[None, :]
        t = template.at[rows[:, None], cols].set(vals)
        t = jax.lax.with_sharding_constraint(t, shard)
        st = sha512_blocks(t.reshape(t.shape[0], INNER_BLOCKS, 32))
        return jax.lax.dynamic_update_slice(buf, st[:, :8], (offset, 0))

    inner = jax.jit(
        inner_body,
        in_shardings=(rep, rep, rep, rep, rep, None),
        out_shardings=rep,
        donate_argnums=0,
    )
    return leaf, inner


def verify_and_count(mesh: Mesh):
    """shard_map pipeline: verify local shard, psum the per-chip valid
    counts over ICI -> (flags [B], total_valid scalar replicated).

    This is the consensus-path shape: 'all validations in this quorum batch
    verified' is a cross-chip reduction, kept on-device.
    """

    def local(a_words, r_words, s_windows, h_digits, s_canonical):
        flags = verify_kernel(a_words, r_words, s_windows, h_digits, s_canonical)
        total = jax.lax.psum(jnp.sum(flags.astype(jnp.int32)), BATCH_AXIS)
        return flags, total

    pspec = P(BATCH_AXIS)
    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(pspec, pspec, pspec, pspec, pspec),
            out_specs=(pspec, P()),
        )
    )
