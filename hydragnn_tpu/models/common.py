"""Shared model components: activations, losses, MLPs, masked batch norm.

Mirrors reference ``hydragnn/utils/model/model.py:30-61`` (activation / loss
selection) with jax-native implementations, plus the padding-aware BatchNorm
that the TPU build needs (the reference uses plain ``BatchNorm1d`` because its
batches are ragged-but-exact; ours carry padded node slots that must not
contaminate the statistics).
"""

from __future__ import annotations

from typing import Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from .radial import shifted_softplus

Array = jax.Array

_ACTIVATIONS: dict[str, Callable[[Array], Array]] = {
    "relu": nn.relu,
    "selu": nn.selu,
    "prelu": lambda x: jnp.where(x >= 0, x, 0.25 * x),  # torch PReLU init slope
    "elu": nn.elu,
    "lrelu_01": lambda x: nn.leaky_relu(x, negative_slope=0.1),
    "lrelu_025": lambda x: nn.leaky_relu(x, negative_slope=0.25),
    "lrelu_05": lambda x: nn.leaky_relu(x, negative_slope=0.5),
    "sigmoid": nn.sigmoid,
    "gelu": nn.gelu,
    "tanh": nn.tanh,
    "silu": nn.silu,
    "swish": nn.silu,  # the name DimeNet++ and Open Catalyst's configurations use
    "shifted_softplus": shifted_softplus,  # SchNet's: ln(1/2 e^x + 1/2)
    "ssp": shifted_softplus,
}


def get_activation(name: str) -> Callable[[Array], Array]:
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"Unknown activation '{name}'; supported: {sorted(_ACTIVATIONS)}"
        )


def _masked_mean(terms: Array, mask: Array, per_row: int,
                 axis_name: str | None = None) -> Array:
    """sum(terms) / (real rows x row width), with numerator AND denominator
    optionally psum'd over a mapped mesh axis first. That makes every masked
    loss exact over a row set PARTITIONED across devices (the halo-exchange
    route: each device holds only its owned nodes) — a mean of per-device
    means would weight devices, not rows. Rows replicated on every device
    (graph-level targets) scale numerator and denominator by the same device
    count, so the psum'd ratio is unchanged there too."""
    s = terms.sum()
    n = mask.sum() * per_row
    if axis_name is not None:
        s = jax.lax.psum(s, axis_name)
        n = jax.lax.psum(n, axis_name)
    return s / jnp.maximum(n, 1.0)


def masked_mse(pred: Array, target: Array, mask: Array,
               axis_name: str | None = None) -> Array:
    """Mean squared error over real (mask=1) rows only."""
    mask = mask.reshape(mask.shape[0], *([1] * (pred.ndim - 1)))
    se = (pred - target) ** 2 * mask
    return _masked_mean(se, mask, pred.shape[-1], axis_name)


def masked_mae(pred: Array, target: Array, mask: Array,
               axis_name: str | None = None) -> Array:
    mask = mask.reshape(mask.shape[0], *([1] * (pred.ndim - 1)))
    ae = jnp.abs(pred - target) * mask
    return _masked_mean(ae, mask, pred.shape[-1], axis_name)


def masked_rmse(pred: Array, target: Array, mask: Array,
                axis_name: str | None = None) -> Array:
    # sqrt OUTSIDE the (cross-device) mean: the global mse then one sqrt —
    # a psum of per-device rmse values would not be any rmse
    return jnp.sqrt(masked_mse(pred, target, mask, axis_name) + 1e-16)


def masked_smooth_l1(pred: Array, target: Array, mask: Array,
                     axis_name: str | None = None) -> Array:
    """torch SmoothL1Loss (beta=1): 0.5 d^2 for |d|<1 else |d|-0.5, mean over
    real rows (reference loss_function_selection, model.py:54-55)."""
    mask = mask.reshape(mask.shape[0], *([1] * (pred.ndim - 1)))
    d = jnp.abs(pred - target)
    huber = jnp.where(d < 1.0, 0.5 * d**2, d - 0.5) * mask
    return _masked_mean(huber, mask, pred.shape[-1], axis_name)


def masked_gaussian_nll(pred: Array, target: Array, mask: Array, var: Array,
                        axis_name: str | None = None) -> Array:
    """torch.nn.GaussianNLLLoss semantics: 0.5*(log(var) + (x-mu)^2/var),
    var clamped below at eps, mean reduction over real rows."""
    eps = 1e-6
    var = jnp.maximum(var, eps)
    mask = mask.reshape(mask.shape[0], *([1] * (pred.ndim - 1)))
    nll = 0.5 * (jnp.log(var) + (pred - target) ** 2 / var) * mask
    return _masked_mean(nll, mask, pred.shape[-1], axis_name)


_LOSSES = {
    "mse": masked_mse,
    "mae": masked_mae,
    "rmse": masked_rmse,
    "smooth_l1": masked_smooth_l1,
}


def get_loss(name: str):
    if name == "GaussianNLLLoss":
        return masked_gaussian_nll
    try:
        return _LOSSES[name]
    except KeyError:
        raise ValueError(f"Unknown loss '{name}'; supported: {sorted(_LOSSES)} or GaussianNLLLoss")


class MLP(nn.Module):
    """Dense stack with activation between layers (last layer linear unless
    ``act_last``)."""

    features: Sequence[int]
    activation: str = "relu"
    act_last: bool = False

    @nn.compact
    def __call__(self, x: Array) -> Array:
        act = get_activation(self.activation)
        n = len(self.features)
        for i, f in enumerate(self.features):
            x = nn.Dense(f, name=f"dense_{i}")(x)
            if i < n - 1 or self.act_last:
                x = act(x)
        return x


# the vmap/shard axis SPMD steps bind for cross-device stat syncing
SYNC_BN_AXIS = "sync_bn"


class MaskedBatchNorm(nn.Module):
    """BatchNorm over valid rows only (padding excluded from statistics).

    Functional equivalent of the per-layer ``BatchNorm(hidden_dim)`` feature
    layers in reference ``Base.py:446-463``; running stats live in the
    ``batch_stats`` collection like flax's own BatchNorm. On multi-device
    meshes, stats are synced across the ``axis_name`` axis when provided —
    the analog of the reference's optional SyncBatchNorm
    (``distributed.py:414-416``).
    """

    momentum: float = 0.9  # torch BatchNorm1d default (1 - torch's 0.1)
    epsilon: float = 1e-5
    axis_name: str | None = None

    @nn.compact
    def __call__(self, x: Array, mask: Array, train: bool = False) -> Array:
        features = x.shape[-1]
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((features,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((features,), jnp.float32)
        )
        scale = self.param("scale", nn.initializers.ones, (features,))
        bias = self.param("bias", nn.initializers.zeros, (features,))

        if train:
            m = mask.reshape(-1, 1).astype(x.dtype)
            # count-weighted sums (not per-replica means): SyncBN then psums
            # raw sums, giving the EXACT union-batch statistics regardless of
            # per-replica counts — and an ALL-masked replica (a fill batch
            # padding a partial device group) contributes zero weight
            # instead of dragging the stats toward 0
            msum = m.sum()
            s1 = (x * m).sum(axis=0)
            if self.axis_name is not None:
                msum = jax.lax.psum(msum, self.axis_name)
                s1 = jax.lax.psum(s1, self.axis_name)
            count = jnp.maximum(msum, 1.0)
            mean = s1 / count
            # second pass centered on the (global) mean: two-pass numerics,
            # and under SyncBN the psum'd centered sums give the EXACT
            # union-batch variance (not the mean of per-replica variances)
            cv = (((x - mean) ** 2) * m).sum(axis=0)
            if self.axis_name is not None:
                cv = jax.lax.psum(cv, self.axis_name)
            var = cv / count
            if not self.is_initializing():
                # EMA gated on real rows: a zero-count batch keeps the old
                # running stats bit-identical (no decay toward 0)
                alpha = (1.0 - self.momentum) * (msum > 0)
                ra_mean.value = ra_mean.value + alpha * (mean - ra_mean.value)
                ra_var.value = ra_var.value + alpha * (var - ra_var.value)
            # FORWARD for a zero-count batch (an all-masked fill replica
            # without SyncBN) uses the running stats: normalizing by
            # mean=0/var=0 would amplify donor features ~1/sqrt(eps) per
            # layer, overflowing deep stacks to inf — and inf * mask(0) is
            # NaN in the loss, poisoning the whole device group's gradients
            mean = jnp.where(msum > 0, mean, ra_mean.value)
            var = jnp.where(msum > 0, var, ra_var.value)
        else:
            mean, var = ra_mean.value, ra_var.value

        y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
        return y * scale + bias


def local_node_index(batch_ids: Array, n_node: Array, num_nodes: int) -> Array:
    """Position of each node within its own graph (0-based) — needed by the
    ``mlp_per_node`` head type (reference ``MLPNode``, ``Base.py:912-982``).

    Works because collate packs each graph's nodes contiguously.
    """
    offsets = jnp.concatenate([jnp.zeros((1,), n_node.dtype), jnp.cumsum(n_node)[:-1]])
    return jnp.arange(num_nodes, dtype=batch_ids.dtype) - offsets[batch_ids]


def equivariant_coordinate_update(
    edge_feat: Array,
    coord_diff: Array,
    senders: Array,
    edge_mask: Array,
    num_nodes: int,
    hidden: int,
    tanh_bound: bool,
    name_prefix: str = "coord",
    hints=None,
) -> Array:
    """Shared E(3) coordinate-update block used by EGNN and SchNet
    (reference ``E_GCL.coord_model`` / ``CFConv.coord_model``): per-edge scalar
    gate MLP (final layer xavier_uniform gain=0.001 == variance_scaling 1e-6),
    optional tanh bound, +/-100 clip, padding mask, sender-mean aggregation.
    Returns the per-node position delta [N, 3].
    """
    from ..graphs import segment

    # must be called from inside a @nn.compact __call__ — the Dense layers
    # attach to the calling module's scope
    gate = nn.Dense(hidden, name=f"{name_prefix}_mlp_0")(edge_feat)
    gate = nn.relu(gate)
    gate = nn.Dense(
        1,
        use_bias=False,
        kernel_init=nn.initializers.variance_scaling(1e-6, "fan_avg", "uniform"),
        name=f"{name_prefix}_mlp_out",
    )(gate)
    if tanh_bound:
        gate = jnp.tanh(gate)
    trans = jnp.clip(coord_diff * gate, -100.0, 100.0) * edge_mask[:, None]
    agg = segment.segment_sum(trans, senders, num_nodes, hints)
    cnt = segment.segment_sum(edge_mask, senders, num_nodes)
    return agg / jnp.maximum(cnt, 1.0)[:, None]
