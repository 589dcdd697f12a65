"""SchNet conv stack (reference ``hydragnn/models/SCFStack.py:42-301``):
continuous-filter convolution. Schuett, Kindermans, Sauceda, Chmiela,
Tkatchenko, Mueller, arXiv:1706.08566. With edge ji = (j -> i) (sender j,
receiver i), ``d = |pos_i - pos_j + shift_ji|``, c the cutoff, G Gaussians
``Delta = c / (G - 1)`` apart, ssp(x) = ln(1/2 e^x + 1/2):

    g_k(d)  = exp(-(d - k Delta)^2 / 2 Delta^2)            k = 0..G-1    [E, G]
    W_ji    = (W_f2 ssp(W_f1 g(d) + b_f1) + b_f2) * 1/2 (cos(pi d / c) + 1)   [E, F]
    m_i     = sum_{j -> i} (W_1 x_j) * W_ji                W_1: no bias  [N, F]
    x_i'    = W_2 m_i + b_2                                              [N, H]

The stack keeps upstream HydraGNN's shape, which wraps PyG's ``CFConv`` alone:
no atom-type embedding (the first layer's ``W_1`` is ``[input_dim, F]``), no
second dense layer and no residual after ``W_2`` (the published interaction
block is ``x + W_3 ssp(W_2 m + b_2) + b_3``); the stack applies the
configuration's activation to ``x'`` after every layer (``models/base.py``).

Every operation of a layer stands under one of five ``jax.named_scope``s, which
the benchmark reads device time by (PERF.md section 3): ``geometry`` (edge
vectors, lengths, cutoff window), ``smearing`` (the Gaussians), ``filter``
(``filter1``, ssp, ``filter2``, the window), ``aggregate`` (``lin1`` and the
gather-multiply-sum) and ``update`` (``lin2``; the coordinate update where
there is one). Lengths, window and Gaussians depend on positions only: where
positions do not move (no ``equivariance``), the first conv layer of a model
call computes them and hands them on in the ``equiv`` slot
(:class:`EdgeBasis`), as DimeNet hands on its bases. The compiler would merge
the five layers' forward expressions by itself; what it does not merge is
their transposes: written once a layer, the force pass scatters ``[E, 3]``
cotangents onto the atoms ten times a step instead of twice (7.5 ms of an
82 ms step on the chip, PERF.md section 6).

Optionally E(3)-equivariant (``equivariance`` config flag): every layer except
the last also nudges positions along normalized edge vectors scaled by a
coordinate MLP of the filters (``CFConv.coord_model``, ``SCFStack.py:243-250``)
— mean-aggregated over incident edges; each layer then makes its own basis
from the positions it is handed. SchNet layers use no batch norm (feature
layers are Identity in the reference, ``_init_conv :81-95``).
"""

from __future__ import annotations

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import ModelSpec
from ..graphs.graph import GraphBatch
from .base import register_conv
from .common import equivariant_coordinate_update
from .radial import GaussianSmearing, cosine_cutoff, shifted_softplus


class EdgeBasis(NamedTuple):
    """What the conv layers of one model call share while positions stay."""

    window: jax.Array  # [E] cosine cutoff x edge mask: a padded edge weighs 0
    rbf: jax.Array  # [E, G]


def _sizes(spec: ModelSpec) -> dict:
    return {
        "filters": spec.num_filters or 64,
        "gaussians": spec.num_gaussians or 50,
        "cutoff": float(spec.radius or 5.0),
    }


def edge_basis(spec: ModelSpec, batch: GraphBatch, pos: jax.Array):
    """(basis, edge vectors ``[E, 3]``, lengths ``[E]``) from positions."""
    s = _sizes(spec)
    with jax.named_scope("geometry"):
        vec = pos[batch.receivers] - pos[batch.senders] + batch.edge_shifts
        dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-12)
        window = cosine_cutoff(dist, s["cutoff"]) * batch.edge_mask
    with jax.named_scope("smearing"):
        rbf = GaussianSmearing(start=0.0, stop=s["cutoff"], num_gaussians=s["gaussians"])(dist)
    return EdgeBasis(window, rbf), vec, dist


@register_conv("SchNet")
class SchNetConv(nn.Module):
    spec: ModelSpec
    layer: int
    out_dim: int | None = None

    feature_norm = False  # reference uses Identity feature layers for SchNet

    @staticmethod
    def describe(spec: ModelSpec) -> str:
        """One line at model build: widths, Gaussians, cutoff, and the static
        route of the gather-multiply-sum (``ops/fused_scatter.py``) for a batch
        whose ``gs_fits`` certificate holds and for one whose does not."""
        from ..ops import fused_scatter as fs
        from ..ops import routing
        from ..utils import flags

        s = _sizes(spec)
        rows = fs.GS_CERT_WINDOW  # the least either kernel takes: the width's verdict
        x = jax.ShapeDtypeStruct((rows, s["filters"]), jnp.float32)

        def route(fits: bool) -> str:
            if fs.gather_scatter_route(x, rows, rows, fits) is None:
                return "fused_gather_scatter (Mosaic)"
            refused = fs.scatter_route(x, rows, rows, fs.segment_window(rows), tiled=True)
            return ("gather x filter -> tiled fused_segment_sum (Mosaic), every gather's "
                    "transpose too" if refused is None else
                    f"XLA gather-multiply-segment_sum ({refused})")

        if routing.default_on(flags.FUSED_SCATTER):
            routes = {fits: route(fits) for fits in (True, False)}
            aggregate = (routes[True] + " whatever gs_fits says" if routes[True] == routes[False]
                         else f"gs_fits held: {routes[True]}; not held: {routes[False]}")
        else:
            aggregate = "XLA gather-multiply-segment_sum (the fused kernel is off on this backend)"
        return (f"SchNet hidden {spec.hidden_dim}, {s['filters']} filters, {s['gaussians']} "
                f"Gaussians, {spec.num_conv_layers} interactions, cutoff {s['cutoff']}, "
                f"activation {spec.activation}; "
                + ("each layer makes its own edge basis (positions move); "
                   if spec.equivariance else "geometry and smearing once a call; ")
                + f"aggregate [E x {s['filters']} -> N]: {aggregate}")

    @nn.compact
    def __call__(
        self, inv: jax.Array, equiv, batch: GraphBatch, train: bool = False
    ):
        spec = self.spec
        hidden = self.out_dim or spec.hidden_dim
        s = _sizes(spec)
        nf = s["filters"]
        last_layer = self.layer >= spec.num_conv_layers - 1
        # the first layer of a call receives positions and makes the basis;
        # the layers after it receive it, unless positions move in between
        if isinstance(equiv, EdgeBasis):
            basis = equiv
        else:
            basis, vec, dist = edge_basis(spec, batch, equiv)

        with jax.named_scope("filter"):
            rbf = basis.rbf
            if spec.edge_dim and batch.edge_attr.shape[1]:
                rbf = jnp.concatenate([rbf, batch.edge_attr], axis=-1)
            w = nn.Dense(nf, name="filter1")(rbf)
            w = shifted_softplus(w)
            w = nn.Dense(nf, name="filter2")(w)
            w = w * basis.window[:, None]

        with jax.named_scope("aggregate"):
            x = nn.Dense(nf, use_bias=False, name="lin1")(inv)
            # sum_{j -> i} x_j * W_ji, placed by ``gather_scatter_route`` from the
            # shapes and the batch's ``gs_fits`` certificate: where the tiled
            # ``fused_segment_sum`` admits ``[E, F]`` rows (F a multiple of
            # 128; any id order, VMEM need independent of N) the declared pair
            # ``segment.gather`` x filter -> ``segment.segment_sum``, so the
            # sum and every gather's transpose in the derivative passes is
            # that kernel; else ``fused_gather_scatter`` (one Mosaic call on
            # 256-row windows) for a certified batch under the resident
            # budget; else XLA's gather, multiply and segment_sum
            from ..ops import gather_scatter_sum

            agg = gather_scatter_sum(
                x, batch.senders, batch.receivers, batch.num_nodes,
                weight=w.astype(x.dtype), hints=batch,
            )

        with jax.named_scope("update"):
            out = nn.Dense(hidden, name="lin2")(agg)
            if spec.equivariance and not last_layer:
                # reference CFConv.coord_model: normalized diff (eps=1.0), sender-
                # mean aggregation (edge_index[0] convention), no tanh bound
                coord_diff = vec / (dist[:, None] + 1.0)
                return out, equiv + equivariant_coordinate_update(
                    w, coord_diff, batch.senders, batch.edge_mask, batch.num_nodes,
                    nf, tanh_bound=False, name_prefix="coord", hints=batch,
                )
        # positions moved before this layer (or may in a conv head after it):
        # hand on what was received
        return out, (equiv if spec.equivariance else basis)
