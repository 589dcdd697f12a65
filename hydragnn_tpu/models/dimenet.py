"""DimeNet++ conv stack (reference ``hydragnn/models/DIMEStack.py:34-328``,
blocks adapted from PyG): directional message passing over edge embeddings,
with angular (triplet) interactions weighted by a spherical Bessel / Legendre
basis. Gasteiger, Giri, Margraf, Guennemann, arXiv:2011.14115 (the layer
equations are DimeNet's, arXiv:2003.03123, with the Hadamard triplet
exchange and the down/up projections of the ++ paper).

With edge ji = (j -> i) (sender j, receiver i), ``vec_ji = pos_i - pos_j +
shift_ji``, d = |vec|, c the cutoff, x = d/c, u the polynomial envelope, z_ln
the n-th root of j_l, s = SiLU:

    rbf_n(d)      = u(x) sin(f_n x),  f_n trained from n pi    n = 1..R   [E, R]
    sbf_ln(d, a)  = u(x) j_l(z_ln x) / |j_{l+1}(z_ln)| P_l(cos a)         [T, S R]
                    (radial part on the E edges, gathered by idx_kj)
    a_(kj,ji)     = atan2(|vec_ji x vec_ki|, vec_ji . vec_ki),  vec_ki = vec_kj + vec_ji
                    (computed as cos a = vec_ji . vec_ki / (|vec_ji| |vec_ki|))
    x_e           = s(W [h_j | h_i | s(W_rbf rbf)])                       [E, H]
    t_(kj,ji)     = s(W_down(s(W_kj x_kj) * W_rbf2 W_rbf1 rbf_kj))[kj]
                    * W_sbf2 W_sbf1 sbf                                   [T, I]
    x'_ji         = s(W_ji x_ji) + s(W_up sum_{kj -> ji} t);
                    residual layers, skip, residual layers                [E, H]
    h'_i          = W_out MLP(W_up' sum_{ji -> i} (W_g rbf_ji) * x'_ji)   [N, .]

The stack keeps upstream HydraGNN's shape (``DIMEStack.get_conv :97-160``),
which departs from the published once-embedded edge state: EVERY conv layer
embeds the incoming node features (a node Linear, then the embedding block
on raw features, no atom-type embedding), runs one interaction block and one
output block, and hands node features on. Other departures: the Legendre part is
plain P_l (its sqrt((2l+1)/4pi) is absorbed by ``lin_sbf1``);
``num_output_layers`` dense layers in the output block (default 1; the paper
and OC20's configuration use 3).

Geometry and both bases depend on positions only: the first conv layer of a
model call computes them (scopes ``geometry``, ``basis``) and hands them on
in the ``equiv`` slot (:class:`TripletBasis`), so they are traced and run
once a call, not once a layer. That layer therefore owns the one trainable
leaf of the bases, the rbf frequencies (``graph_convs_0/rbf/freq``, started
at n pi: PyG's and upstream's one shared ``BesselBasisLayer``).

Triplets are enumerated on the host and padded (``graphs/triplets.py``: every
(kj, ji) but the exact reverse, periodic images kept; ``graphs/batching.py``:
``max_neighbours x n_edge`` slots a bucket); angles are computed on the device
from padded edge vectors — vectors first, then sum, to stay correct under PBC
(``_embedding :176-183``). The triplet dimension has two layouts, told apart
by the batch's static ``meta.triplet_rows`` (the corpus's, ``_exchange``
below), and the layer's body is one:

* the dense block ``[E, K]`` where one side of every atom's edges is capped at
  K for the whole corpus: slot ``(r, s)`` pairs row edge ``r`` with the s-th
  edge its shared atom sends (rows kj) or receives (rows ji). No index of the
  triplet dimension's length exists: the row side is a broadcast and a sum
  over an axis, the partner side a gather of ``[N, K C]`` rows by the row's
  atom and a sum of ``[E, K C]`` rows onto the N atoms, with an E-level
  placement through the ``[N, K]`` table;
* the flat list (``idx_kj`` / ``idx_ji`` ``[T]``) for samples that carry their
  own lists (serving, corpora with no cap): gathers and sums keyed by them.

Every gather and sum goes through ``graphs/segment.py`` (node-level ones with
the batch's certificates, the exchange's with theirs stated as not held:
``_UNCERTIFIED`` below), so their transposes in the force and grad-of-grad
passes are sums again.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import ModelSpec
from ..graphs.graph import GraphBatch
from ..graphs import segment
from .base import register_conv
from .radial import BesselBasis
from .spherical import angular_on_triplets, radial_on_edges


# The exchange's gathers and sums state their certificate themselves: collate
# certifies nothing about their ids, and a ``None`` here would put the resident
# kernel AND its in-program fallback into every pass of the step. ``False``
# runs the tiled Pallas sum where the rows are whole lanes
# (``ops/fused_scatter.py``: exact for any id order, no certificate) and XLA's
# sum elsewhere. Flat list: ``[T, 64]`` rows are half a lane row, so both
# sides are XLA's. Block: the ``[E, K I]`` rows (3,200 = 25 x 128 at OC20's
# sizes) onto N atoms take the tiled sum at every bucket of 128 atom slots or
# more (PR 38; before it only past the resident rule, ~400 atom slots); the
# ``[N K, 64]`` placement through the table stays XLA's.
_UNCERTIFIED = False


class TripletBasis(NamedTuple):
    """What every conv layer of one model call shares."""

    rbf: jax.Array  # [E, R]
    sbf: jax.Array  # [T, S * R]; [E, K, S * R] on the block layout


class _Exchange(NamedTuple):
    """The triplet dimension's primitives: an ``[E, C]`` array's rows brought
    to the triplets of which they are the kj / the ji edge, triplet rows summed
    onto their ji edge, and the mask in the triplets' shape (``[T]`` flat,
    ``[E, K]`` block: the arrays between them are ``[T, C]`` / ``[E, K, C]``)."""

    from_kj: Callable
    from_ji: Callable
    onto_ji: Callable
    mask: jax.Array


def _exchange(batch: GraphBatch) -> _Exchange:
    """The batch's layout, from its static meta: the only lines of the layer
    that differ between the flat list and the block."""
    E, N = batch.num_edges, batch.num_nodes
    rows = batch.meta.triplet_rows if batch.meta is not None else None
    if rows is None:
        if batch.idx_kj.shape != batch.triplet_mask.shape:
            raise ValueError(
                f"triplet indices {batch.idx_kj.shape} do not match the mask "
                f"{batch.triplet_mask.shape}: a block-layout batch without its meta "
                f"(graphs.batching.flat_triplets turns it into lists BEFORE the meta goes)")
        return _Exchange(
            from_kj=lambda x: segment.gather(x, batch.idx_kj, fits=_UNCERTIFIED),
            from_ji=lambda x: segment.gather(x, batch.idx_ji, fits=_UNCERTIFIED),
            onto_ji=lambda t: segment.segment_sum(t, batch.idx_ji, E, fits=_UNCERTIFIED),
            mask=batch.triplet_mask,
        )
    # rows kj: the partners ji of every edge that ENDS at atom j are the K edges
    # j sends; rows ji: the partners kj of every edge that STARTS at j are the K
    # edges j receives. Either way one table row an atom, keyed by the row's j
    table, atom = ((batch.idx_ji, batch.receivers) if rows == "kj"
                   else (batch.idx_kj, batch.senders))
    K = table.shape[1]
    table = table.reshape(N * K)

    def from_row(x):
        return x[:, None, :]

    def from_partner(x):
        by_atom = segment.gather(x, table, fits=_UNCERTIFIED).reshape(N, K * x.shape[1])
        return segment.gather(by_atom, atom, fits=_UNCERTIFIED).reshape(E, K, x.shape[1])

    def onto_row(t):
        return t.sum(axis=1)

    def onto_partner(t):
        by_atom = segment.segment_sum(t.reshape(E, K * t.shape[2]), atom, N, fits=_UNCERTIFIED)
        # an empty table slot reads E - 1 and its rows are exact zeros (masked)
        return segment.segment_sum(by_atom.reshape(N * K, t.shape[2]), table, E, fits=_UNCERTIFIED)

    kj_is_row = rows == "kj"
    return _Exchange(
        from_kj=from_row if kj_is_row else from_partner,
        from_ji=from_partner if kj_is_row else from_row,
        onto_ji=onto_partner if kj_is_row else onto_row,
        mask=batch.triplet_mask.reshape(E, K),
    )


def _sizes(spec: ModelSpec) -> dict:
    return {
        "hidden": max(spec.hidden_dim, 2),
        "out_emb": spec.out_emb_size or 128,
        "int_emb": spec.int_emb_size or 64,
        "basis_emb": spec.basis_emb_size or 8,
        "num_radial": spec.num_radial or 6,
        "num_spherical": spec.num_spherical or 7,
        "envelope_exponent": spec.envelope_exponent or 5,
        "cutoff": float(spec.radius or 5.0),
        "before_skip": spec.num_before_skip or 1,
        "after_skip": spec.num_after_skip or 2,
        "output_layers": spec.num_output_layers or 1,
    }


def triplet_basis(spec: ModelSpec, batch: GraphBatch, rbf_of) -> TripletBasis:
    """rbf on the edges, sbf on the triplets, from the batch's positions.
    ``rbf_of`` maps scaled lengths ``[E]`` to ``[E, R]`` (the calling layer's
    :class:`BesselBasis`, which holds the frequencies)."""
    s = _sizes(spec)
    with jax.named_scope("geometry"):
        vec = (segment.gather(batch.pos, batch.receivers, batch)
               - segment.gather(batch.pos, batch.senders, batch) + batch.edge_shifts)
        dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-18)
        # a padded edge is handed the cutoff itself, where both envelopes and
        # their derivatives vanish: BEFORE the math (see below)
        x = jnp.where(batch.edge_mask > 0, dist / s["cutoff"], 1.0)
        # the angle at the shared vertex enters through its cosine alone
        # (P_l(cos a)), and |vec_ji x vec_ki|^2 + (vec_ji . vec_ki)^2 =
        # |vec_ji|^2 |vec_ki|^2, so cos(atan2(|cross|, dot)) is the normalised
        # dot product: no cross product, no atan2, no singular derivative at
        # collinear triplets. Vectors first, then sum — PBC-safe. A padded
        # triplet's two vectors are zero: its dot and norms are replaced with
        # constants BEFORE the division (jnp.where routes cotangents only to
        # the selected branch; 0 * NaN = NaN would defeat masking afterwards)
        triplets = _exchange(batch)
        tm = triplets.mask > 0
        pos_ji = triplets.from_ji(vec)
        pos_ki = triplets.from_kj(vec) + pos_ji
        dot = jnp.where(tm, jnp.sum(pos_ji * pos_ki, axis=-1), 1.0)
        norms = jnp.where(
            tm, jnp.sum(pos_ji * pos_ji, axis=-1) * jnp.sum(pos_ki * pos_ki, axis=-1), 1.0)
        cos_angle = dot * jax.lax.rsqrt(norms)
    with jax.named_scope("basis"):
        rbf = rbf_of(x)
        # The two parts of sbf are computed as programs of their own
        # (``optimization_barrier`` on what goes in and what comes out, which
        # their cotangents pass too). Fused with their neighbours, the TPU's
        # compiler gave NaN forces for every real atom of some batches (one of
        # the first three at OC20's sizes; the energies finite, the CPU finite,
        # every route on XLA; PERF.md section 6, PR 35): barriers at the sums,
        # the gathers or the activations left it, these four end it. Each costs
        # one pass over an ``[E]`` / ``[T]`` / ``[T, S R]`` array.
        held = jax.lax.optimization_barrier
        radial = held(radial_on_edges(
            held(x), s["num_spherical"], s["num_radial"], s["envelope_exponent"]))
        angular = held(angular_on_triplets(held(cos_angle), s["num_spherical"], s["num_radial"]))
        sbf = triplets.from_kj(radial) * angular
    return TripletBasis(rbf, sbf)


class ResidualLayer(nn.Module):
    hidden: int

    @nn.compact
    def __call__(self, x):
        h = nn.silu(nn.Dense(self.hidden, name="lin1")(x))
        h = nn.silu(nn.Dense(self.hidden, name="lin2")(h))
        return x + h


class InteractionPPBlock(nn.Module):
    hidden: int
    int_emb_size: int
    basis_emb_size: int
    num_before_skip: int
    num_after_skip: int

    @nn.compact
    def __call__(self, x, basis: TripletBasis, batch: GraphBatch):
        with jax.named_scope("dense"):
            # basis transforms (bias-free, PyG InteractionPPBlock)
            rbf_e = nn.Dense(self.basis_emb_size, use_bias=False, name="lin_rbf1")(basis.rbf)
            rbf_e = nn.Dense(self.hidden, use_bias=False, name="lin_rbf2")(rbf_e)
            x_ji = nn.silu(nn.Dense(self.hidden, name="lin_ji")(x))
            x_kj = nn.silu(nn.Dense(self.hidden, name="lin_kj")(x)) * rbf_e
        with jax.named_scope("triplets"):
            # messages from edge kj weighted by the angular basis, summed onto
            # edge ji; the mask multiplies last, so a padded slot adds exactly 0
            x_kj = nn.silu(nn.Dense(self.int_emb_size, name="lin_down")(x_kj))
            sbf_e = nn.Dense(self.basis_emb_size, use_bias=False, name="lin_sbf1")(basis.sbf)
            sbf_e = nn.Dense(self.int_emb_size, use_bias=False, name="lin_sbf2")(sbf_e)
            triplets = _exchange(batch)
            x_kj = triplets.onto_ji(triplets.from_kj(x_kj) * sbf_e * triplets.mask[..., None])
            x_kj = nn.silu(nn.Dense(self.hidden, name="lin_up")(x_kj))
        with jax.named_scope("dense"):
            h = x_ji + x_kj
            for i in range(self.num_before_skip):
                h = ResidualLayer(self.hidden, name=f"res_before_{i}")(h)
            h = nn.silu(nn.Dense(self.hidden, name="lin")(h)) + x
            for i in range(self.num_after_skip):
                h = ResidualLayer(self.hidden, name=f"res_after_{i}")(h)
        return h


@register_conv("DimeNet")
class DimeNetConv(nn.Module):
    spec: ModelSpec
    layer: int
    out_dim: int | None = None

    feature_norm = False  # reference DIMEStack uses Identity feature layers

    @staticmethod
    def describe(spec: ModelSpec) -> str:
        """One line at model build: widths, basis sizes, the triplet pad rule
        and the layouts the triplet dimension takes under it."""
        s = _sizes(spec)
        pad = (f"at most {spec.max_neighbours} x n_edge slots a bucket (max_neighbours); layout: "
               f"dense [E, K] block, rows kj where no atom sends more than K edges, rows ji "
               f"where none receives more (the loader's choice, in the batch's meta), else flat"
               if spec.max_neighbours else
               "from the samples' attached triplet counts; layout: flat list (idx_kj, idx_ji)")
        return (f"DimeNet++ hidden {s['hidden']}, out_emb {s['out_emb']}, int_emb {s['int_emb']}, "
                f"basis_emb {s['basis_emb']}, {spec.num_conv_layers} layers, sbf "
                f"{s['num_spherical']} x {s['num_radial']}, envelope {s['envelope_exponent']}, "
                f"cutoff {s['cutoff']}, residual {s['before_skip']}+{s['after_skip']}, "
                f"{s['output_layers']} output layer(s); radial part on edges, basis once a call; "
                f"triplet pad: {pad}")

    @nn.compact
    def __call__(
        self, inv: jax.Array, equiv, batch: GraphBatch, train: bool = False
    ):
        spec = self.spec
        s = _sizes(spec)
        hidden = s["hidden"]
        out_dim = self.out_dim or spec.hidden_dim
        if batch.triplet_mask.shape[0] == 0:
            raise ValueError(
                "DimeNet needs a triplet pad dimension: set Architecture.max_neighbours "
                "(graphs.batching sizes it) or attach triplets in preprocessing "
                "(hydragnn_tpu.graphs.triplets.attach_triplets)"
            )
        # the first layer of a call receives positions and makes the bases;
        # the layers after it receive them
        basis = equiv if isinstance(equiv, TripletBasis) else triplet_basis(
            spec, batch, BesselBasis(  # on scaled lengths: its cutoff is 1
                num_radial=s["num_radial"], cutoff=1.0,
                envelope_exponent=s["envelope_exponent"], name="rbf"))

        with jax.named_scope("embedding"):
            # node Linear + EmbeddingBlock (HydraEmbeddingBlock: features not
            # atomic-number embeddings)
            h = nn.Dense(hidden, name="lin_node")(inv)
            rbf_emb = nn.silu(nn.Dense(hidden, name="emb_lin_rbf")(basis.rbf))
            feats = [segment.gather(h, batch.senders, batch),
                     segment.gather(h, batch.receivers, batch), rbf_emb]
            if spec.edge_dim and batch.edge_attr.shape[1]:
                feats.append(batch.edge_attr)
            x_edge = nn.silu(
                nn.Dense(hidden, name="emb_lin")(jnp.concatenate(feats, axis=-1))
            )

        x_edge = InteractionPPBlock(
            hidden=hidden,
            int_emb_size=s["int_emb"],
            basis_emb_size=s["basis_emb"],
            num_before_skip=s["before_skip"],
            num_after_skip=s["after_skip"],
            name="interaction",
        )(x_edge, basis, batch)

        with jax.named_scope("output"):
            # OutputPPBlock: rbf-gated edge -> node sum, then its MLP
            g = nn.Dense(hidden, use_bias=False, name="out_lin_rbf")(basis.rbf)
            x_gated = g * x_edge * batch.edge_mask[:, None]
            node_x = segment.segment_sum(x_gated, batch.receivers, batch.num_nodes, hints=batch)
            node_x = nn.Dense(s["out_emb"], use_bias=False, name="out_lin_up")(node_x)
            for i in range(s["output_layers"]):
                node_x = nn.silu(nn.Dense(s["out_emb"], name=f"out_lin_{i}")(node_x))
            node_x = nn.Dense(out_dim, use_bias=False, name="out_lin")(node_x)
        return node_x, basis
