"""The conv stack as ONE scanned layer body (``Training.scan_conv_layers``).

``HydraModel.encode`` unrolls ``num_conv_layers`` Python iterations into one
program, and an energy-and-force step emits each of them four times (forward,
forces, and both again under the parameter gradient). At ten 384-wide GPS
layers that is a step program of 208.6 MB serialized, over what the chip
machines' compile cache holds, compiled anew in every run (PERF.md section 6,
"GPS, three attempts"). Here the blocks that share one pytree of parameter and
batch-statistic shapes run under ``lax.scan`` over their stacked subtrees: one
layer's HLO, as many iterations as the run has blocks.

Two users, one body. ``parallel/pipeline.py`` stacks blocks ``1..L-1`` and
scans ``apply_block`` inside each stage of its ring; ``scanned_apply`` (what
``HydraModel.apply`` becomes when the key is set) scans the longest
homogeneous run of one device's stack and runs the blocks before and after it
as they always ran.

The parameter tree keeps its names and layout (``graph_convs_i/...``,
``feature_norm_i``): stacking happens inside the program, every layer's
updated batch statistics leave the scan and land in their own subtree, so
checkpoints, the optimizer state and ``init`` (which keeps the Python loop: it
makes the per-layer subtrees) know nothing of the scan.

What a scanned block may NOT do is behave by its ``layer`` index alone: the
body is traced once, at the run's first block, and applied with every block's
values. Every registered stack that treats a layer differently (the first
lifts ``input_dim``, the last of an equivariant stack moves no coordinates)
also gives it other parameter shapes, which is what ends a run; unless the
block only LACKS subtrees that its conv class names ``inert_at_zero`` (EGNN's
coordinate gate: at all-zero parameters it moves nothing, which is what the
last layer does without one): such a block runs in the body with zeros there,
so a ten-layer equivariant stack is one body and not two.
"""

from __future__ import annotations

from collections.abc import Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp

# the layer's four subtrees -> where they live in the variables
_SUBTREES = (
    ("conv", "params", "graph_convs_{}"),
    ("norm_p", "params", "feature_norm_{}"),
    ("conv_s", "batch_stats", "graph_convs_{}"),
    ("norm_s", "batch_stats", "feature_norm_{}"),
)


def layer_tree(params: dict, stats: dict, i: int) -> dict:
    """Block ``i``'s own subtrees: conv and feature-norm parameters, conv and
    feature-norm batch statistics (each only where the stack has it)."""
    cols = {"params": params, "batch_stats": stats}
    return {key: cols[col][name.format(i)] for key, col, name in _SUBTREES
            if name.format(i) in cols[col]}


def _shapes(tree):
    return jax.tree.map(jnp.shape, tree)


def fill_inert(tree, like, inert: tuple = ()):
    """``tree`` with zeros in place of each subtree of ``like`` it lacks and
    ``inert`` names; None where the two differ by anything else (a name only
    ``tree`` has, a shape, a missing subtree ``inert`` does not name)."""
    if not isinstance(like, Mapping):
        return tree if jnp.shape(tree) == jnp.shape(like) else None
    if not isinstance(tree, Mapping) or set(tree) - set(like):
        return None
    out = {}
    for name, sub in like.items():
        if name in tree:
            out[name] = fill_inert(tree[name], sub, inert)
        elif name in inert:
            out[name] = jax.tree.map(jnp.zeros_like, sub)
        else:
            return None
        if out[name] is None:
            return None
    return out


def stack_layers(params: dict, stats: dict, start: int, stop: int, inert: tuple = ()) -> dict:
    """Blocks ``start..stop-1`` stacked to one ``[stop - start, ...]`` pytree,
    a block that lacks a subtree ``inert`` names given zeros there
    (``fill_inert``). Raises where their subtrees are not shape-homogeneous."""
    trees = [layer_tree(params, stats, i) for i in range(start, stop)]
    filled = [fill_inert(t, trees[0], inert) for t in trees]
    if any(t is None for t in filled):
        raise ValueError(
            f"conv blocks {start}..{stop - 1} are not parameter-homogeneous; "
            f"got per-layer shapes {[_shapes(t) for t in trees]}"
        )
    return jax.tree.map(lambda *xs: jnp.stack(xs), *filled)


def homogeneous_run(params: dict, stats: dict, num_layers: int,
                    inert: tuple = ()) -> tuple[int, int]:
    """``(start, stop)`` of the longest run of consecutive blocks whose
    subtrees share one pytree of shapes with the run's first, or lack only
    subtrees ``inert`` names (the first of the longest)."""
    trees = [layer_tree(params, stats, i) for i in range(num_layers)]
    best, start = (0, 0), 0
    for i in range(1, num_layers + 1):
        if i == num_layers or fill_inert(trees[i], trees[start], inert) is None:
            if i - start > best[1] - best[0]:
                best = (start, i)
            start = i
    return best


def apply_block(model, params: dict, stats: dict, i: int, p_tree: dict,
                inv, equiv, batch, train: bool, collect: bool, rngs=None):
    """``model.conv_block(i)`` with ``p_tree``'s values in block ``i``'s
    places: the scanned body. Returns ``((inv, equiv), updates)``, ``updates``
    the block's batch statistics after the call (``conv_s`` / ``norm_s``
    where it has them; empty unless ``collect``)."""
    cols = {"params": dict(params), "batch_stats": dict(stats)}
    for key, col, name in _SUBTREES:
        if key in p_tree:
            cols[col][name.format(i)] = p_tree[key]
    variables = {"params": cols["params"]}
    if cols["batch_stats"]:
        variables["batch_stats"] = cols["batch_stats"]
    block = type(model).conv_block
    # ``nn.Module.apply``: below ``HydraModel.apply``'s dispatch to the scan
    if not collect:
        return nn.Module.apply(model, variables, i, inv, equiv, batch, train,
                               method=block, rngs=rngs), {}
    out, upd = nn.Module.apply(model, variables, i, inv, equiv, batch, train, method=block,
                               mutable=["batch_stats"], rngs=rngs)
    upd = upd.get("batch_stats", {})
    return out, {key: upd[name.format(i)] for key, col, name in _SUBTREES
                 if col == "batch_stats" and name.format(i) in upd}


def _fold(rngs, i):
    """Each block draws its own dropout mask: the layer index folded in."""
    return None if rngs is None else {k: jax.random.fold_in(v, i) for k, v in rngs.items()}


def scanned_apply(model, variables, batch, train: bool = False, mutable=False, rngs=None,
                  layer_hook=None, pool_reduce=None):
    """``model.apply(variables, batch, train)`` with the longest homogeneous
    run of conv blocks as one ``lax.scan``. Answers what ``apply`` answers:
    the outputs, or ``(outputs, {"batch_stats": ...})`` where ``mutable``
    names the statistics."""
    from .base import CONV_REGISTRY

    spec, cls = model.spec, type(model)
    if layer_hook is not None:
        raise ValueError(
            "scan_conv_layers does not compose with a layer_hook (the halo route refreshes "
            "boundary rows between layers from outside the stack); unset "
            "Training.scan_conv_layers")
    if getattr(CONV_REGISTRY[spec.mpnn_type], "collect_layer_outputs", False):
        raise ValueError(
            f"scan_conv_layers: {spec.mpnn_type} reads every layer's output "
            "(collect_layer_outputs), which a scanned stack does not keep")
    collect = bool(mutable)
    if collect and tuple(mutable) != ("batch_stats",):
        raise ValueError(f"scan_conv_layers: mutable={mutable!r}; only ['batch_stats'] is carried")
    params = variables["params"]
    stats = variables.get("batch_stats", {})
    new_stats = dict(stats)
    num_layers = spec.num_conv_layers
    inert = getattr(CONV_REGISTRY[spec.mpnn_type], "inert_at_zero", ())

    def keep(upd, i, pick=lambda x: x):
        """Block ``i``'s moved statistics into their own subtrees."""
        for key, _, name in _SUBTREES:
            if key in upd:
                new_stats[name.format(i)] = jax.tree.map(pick, upd[key])

    def unrolled(i, inv, equiv):
        out, upd = apply_block(model, params, stats, i, {}, inv, equiv, batch, train,
                               collect, _fold(rngs, i))
        keep(upd, i)
        return out

    def body(carry, xs):
        p_tree, i = xs
        return apply_block(model, params, stats, start, p_tree, *carry, batch, train,
                           collect, _fold(rngs, i))

    # the scopes ``HydraModel.__call__`` / ``.encode`` would have opened: the
    # trace's readers find a conv block's operations by this path
    scope = cls.__name__
    with jax.named_scope(scope), jax.named_scope(f"{scope}.encode"):
        inv, equiv = nn.Module.apply(model, variables, batch, method=cls.embed)
        start, stop = homogeneous_run(params, stats, num_layers, inert)
        for i in range(start):
            inv, equiv = unrolled(i, inv, equiv)
        # a block that hands on another carry than it got (PaiNN's first makes
        # the vector channel from positions) runs before the scan, not in it
        while stop - start >= 2:
            first = (layer_tree(params, stats, start), jnp.int32(start))
            if jax.eval_shape(lambda c: body(c, first)[0], (inv, equiv)) == \
                    jax.eval_shape(lambda c: c, (inv, equiv)):
                break
            inv, equiv = unrolled(start, inv, equiv)
            start += 1
        if stop - start < 2:
            raise ValueError(
                f"scan_conv_layers: no two consecutive conv blocks of this {spec.mpnn_type} stack "
                f"({num_layers} layers) share their parameter shapes and hand on what they were "
                "handed; unset Training.scan_conv_layers")
        stacked = stack_layers(params, stats, start, stop, inert)
        (inv, equiv), upds = jax.lax.scan(
            body, (inv, equiv), (stacked, jnp.arange(start, stop, dtype=jnp.int32)))
        for i in range(start, stop):
            keep(upds, i, lambda x: x[i - start])
        for i in range(stop, num_layers):
            inv, equiv = unrolled(i, inv, equiv)

    head_vars = dict(variables, batch_stats=new_stats) if new_stats else variables
    with jax.named_scope(scope):
        if not collect:
            return nn.Module.apply(model, head_vars, inv, equiv, batch, train, pool_reduce,
                                   method=cls.decode, rngs=rngs)
        out, upd = nn.Module.apply(model, head_vars, inv, equiv, batch, train, pool_reduce,
                                   method=cls.decode, mutable=["batch_stats"], rngs=rngs)
    new_stats.update(upd.get("batch_stats", {}))
    return out, {"batch_stats": new_stats}
