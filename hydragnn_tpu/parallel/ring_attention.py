"""Ring attention over node-sharded graphs — giant-graph global attention.

The brief's long-context requirement (ring / all-to-all context parallelism)
applied to graph learning: GPS global attention over ONE giant graph whose
node arrays are sharded across the mesh. Dense attention materializes
[N, N] logits — impossible at scale; ring attention never does:

* q/k/v stay sharded over the ``data`` axis ([N/D rows per device]);
* the K/V (+ graph-id/mask) shard rotates around the mesh ring via
  ``lax.ppermute`` (ICI neighbor hops, D-1 of them);
* each device folds one K/V block per hop into an ONLINE softmax
  (running max / denominator / weighted accumulator — the flash-attention
  recurrence), so peak memory is O(N/D · H · d) regardless of N.

Same-graph masking (``batch_ids`` equality) makes this the sharded
equivalent of ``GraphMultiheadAttention._flat_attention``; parity is tested
against it on the virtual 8-device mesh.

Used by GPS when ``global_attn_type: "ring"`` with an active mesh (set by
``run_training`` via ``set_global_mesh``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS

_NEG = -1e9

# Trace-time mesh context: the model module can't carry a Mesh (it's not a
# pytree leaf), so run_training publishes the active mesh here before the
# step is traced.
_GLOBAL_MESH: Mesh | None = None


def set_global_mesh(mesh: Mesh | None) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> Mesh | None:
    return _GLOBAL_MESH


def ring_attention(
    q: jax.Array,  # [N, H, Dh] node-sharded
    k: jax.Array,
    v: jax.Array,
    batch_ids: jax.Array,  # [N] graph id per node
    node_mask: jax.Array,  # [N] 1 for real nodes
    mesh: Mesh,
) -> jax.Array:
    """Masked same-graph softmax attention with rotating K/V shards."""
    n_dev = mesh.shape[DATA_AXIS]
    N, H, Dh = q.shape
    if N % n_dev:
        raise ValueError(f"node count {N} must divide the data axis ({n_dev})")
    scale = 1.0 / math.sqrt(Dh)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def local(q_b, bid_q, k_b, v_b, bid_kv, m_kv):
        # shard_map gives block-local arrays [n, ...]
        n = q_b.shape[0]

        def rotate(x):
            return jax.lax.ppermute(x, DATA_AXIS, perm)

        mx0 = jnp.full((n, H), _NEG, q_b.dtype)
        den0 = jnp.zeros((n, H), q_b.dtype)
        acc0 = jnp.zeros((n, H, Dh), q_b.dtype)

        def body(_, carry):
            k_c, v_c, bid_c, m_c, mx, den, acc = carry
            logits = jnp.einsum("nhd,mhd->nhm", q_b, k_c) * scale
            valid = (bid_q[:, None] == bid_c[None, :]) & (m_c[None, :] > 0)
            logits = jnp.where(valid[:, None, :], logits, _NEG)
            blk_mx = logits.max(axis=-1)  # [n, H]
            new_mx = jnp.maximum(mx, blk_mx)
            corr = jnp.exp(mx - new_mx)
            p = jnp.exp(logits - new_mx[..., None]) * valid[:, None, :]
            den = den * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("nhm,mhd->nhd", p, v_c)
            return (rotate(k_c), rotate(v_c), rotate(bid_c), rotate(m_c),
                    new_mx, den, acc)

        carry = (k_b, v_b, bid_kv, m_kv, mx0, den0, acc0)
        carry = jax.lax.fori_loop(0, n_dev, body, carry)
        _, _, _, _, _, den, acc = carry
        return acc / jnp.maximum(den, 1e-20)[..., None]

    split = P(DATA_AXIS)
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(split, split, split, split, split, split),
        out_specs=split,
        check_vma=False,
    )(q, batch_ids, k, v, batch_ids, node_mask)
