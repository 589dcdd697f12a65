"""``Training.scan_conv_layers``: the homogeneous conv blocks as one
``lax.scan`` (``models/layer_scan.py``) against the unrolled stack, on the CPU.

For GPS round EGNN (batch statistics in every layer; the last layer has no
coordinate gate and runs in the body with zeros for one, ``inert_at_zero``),
plain EGNN and SchNet (the first layer hands on an edge
basis, not positions): energies, forces, the parameter gradient and the new
batch statistics of one energy-and-force step, and the loss and gradient of
plain training; the parameter tree with the key on and off; what the scan
refuses. The pipeline's tests (``tests/test_pipeline.py``) run the same body.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.config import update_config
from hydragnn_tpu.datasets import deterministic_graph_data
from hydragnn_tpu.graphs.batching import collate, compute_pad_spec
from hydragnn_tpu.models import create_model_config, layer_scan
from hydragnn_tpu.models.base import CONV_REGISTRY
from hydragnn_tpu.models.mlip import make_energy_and_forces, make_graph_energy_fn
from hydragnn_tpu.preprocess import apply_variables_of_interest
from hydragnn_tpu.preprocess.encodings import attach_lap_pe
from hydragnn_tpu.train.step import energy_force_objective, model_objective

from test_config import CI_CONFIG

STACKS = {
    # name: (architecture keys, blocks the scan holds of 5)
    "gps_egnn": ({"mpnn_type": "EGNN", "global_attn_engine": "GPS", "global_attn_heads": 2,
                  "pe_dim": 3, "equivariance": True}, (0, 5)),
    "egnn": ({"mpnn_type": "EGNN", "equivariance": True}, (1, 5)),
    "schnet": ({"mpnn_type": "SchNet", "num_gaussians": 10, "num_filters": 8}, (1, 5)),
}


def build(stack: str, scan: bool, mlip: bool = True, layers: int = 5, **arch_keys):
    cfg = copy.deepcopy(CI_CONFIG)
    arch = cfg["NeuralNetwork"]["Architecture"]
    arch.update(STACKS[stack][0], num_conv_layers=layers, hidden_dim=8,
                activation_function="silu", **{"dropout": 0.0, **arch_keys})
    if mlip:
        arch.update(enable_interatomic_potential=True, energy_weight=1.0, force_weight=10.0,
                    graph_pooling="add",
                    output_heads={"node": {"num_headlayers": 1, "dim_headlayers": [8],
                                           "type": "mlp"}})
        cfg["NeuralNetwork"]["Variables_of_interest"].update(
            output_index=[0], type=["node"], output_dim=[1], output_names=["energy"])
        cfg["NeuralNetwork"]["Architecture"]["task_weights"] = [1.0]
    cfg["NeuralNetwork"]["Training"]["scan_conv_layers"] = scan
    samples = deterministic_graph_data(number_configurations=8, seed=17)
    samples = apply_variables_of_interest(samples, cfg)
    rng = np.random.default_rng(3)
    for s in samples:
        if "pe_dim" in arch and arch.get("global_attn_engine"):
            attach_lap_pe(s, arch["pe_dim"])
        s.energy_y = rng.normal(size=1).astype(np.float32)
        s.forces_y = rng.normal(size=(s.num_nodes, 3)).astype(np.float32)
    cfg = update_config(cfg, samples)
    model = create_model_config(cfg)
    batch = jax.tree.map(jnp.asarray, collate(samples[:4], compute_pad_spec(samples, 4)))
    return model, batch


def seeded(variables):
    """Distinct values in every leaf, the statistics' too: a scan that mixed
    two layers' subtrees up would show."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(variables)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))

    def moved(key, path, leaf):
        noise = jax.random.normal(key, leaf.shape, leaf.dtype)
        if jax.tree_util.keystr(path).endswith("['var']"):  # a variance stays positive
            return leaf * jnp.exp(0.3 * noise)
        return leaf + 0.3 * noise * (1 + jnp.abs(leaf))

    return jax.tree.unflatten(treedef, [moved(k, path, leaf)
                                        for k, (path, leaf) in zip(keys, leaves)])


def close(a, b, what, rtol=1e-5):
    """Worst gap of a leaf against its largest entry, or the median leaf's
    if that is larger: a bias before a batch norm has a gradient of rounding
    alone (1e-7 of its neighbours'), which no two programs round alike."""
    floor = float(np.median([np.abs(np.asarray(x)).max() for x in jax.tree.leaves(a)]))
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        scale = max(np.abs(x).max(), floor, 1e-6)
        assert np.abs(x - y).max() <= rtol * scale, (what, jax.tree_util.keystr(path))


@pytest.fixture(scope="module", params=sorted(STACKS))
def pair(request):
    unrolled, batch = build(request.param, scan=False)
    scanned, _ = build(request.param, scan=True)
    variables = unrolled.init(jax.random.PRNGKey(0), batch, train=False)
    again = scanned.init(jax.random.PRNGKey(0), batch, train=False)
    # init keeps the Python loop: one tree, whatever the key says
    assert jax.tree.structure(variables) == jax.tree.structure(again)
    close(variables, again, "init", rtol=0.0)
    variables = seeded({"params": variables["params"],
                        "batch_stats": variables.get("batch_stats", {})})
    return request.param, unrolled, scanned, variables, batch


def test_the_run_the_scan_holds(pair):
    name, _, scanned, variables, _ = pair
    inert = getattr(CONV_REGISTRY[scanned.spec.mpnn_type], "inert_at_zero", ())
    run = layer_scan.homogeneous_run(variables["params"], variables["batch_stats"],
                                     scanned.spec.num_conv_layers, inert)
    assert run == STACKS[name][1]
    if inert:  # without the conv class's word the gate-less last layer ends the run
        assert layer_scan.homogeneous_run(
            variables["params"], variables["batch_stats"],
            scanned.spec.num_conv_layers) == (run[0], run[1] - 1)


def test_energies_forces_and_statistics_under_the_mlip_step(pair):
    name, unrolled, scanned, variables, batch = pair
    out = {}
    for model in (unrolled, scanned):
        graph_e, forces = jax.jit(make_energy_and_forces(model))(variables, batch)
        energy_fn = make_graph_energy_fn(model)
        _, stats = jax.jit(lambda v, b: energy_fn(v, b.pos, b, train=True))(variables, batch)
        out[model.spec.scan_conv_layers] = (graph_e, forces, stats)
    assert jax.tree.structure(out[False]) == jax.tree.structure(out[True])
    close(out[False], out[True], name)
    if name == "gps_egnn":  # every layer's statistics moved, each into its own subtree
        stats = out[True][2]
        assert sorted(stats) == [f"graph_convs_{i}" for i in range(5)]
        for i in range(5):
            for norm in ("norm1", "norm2", "norm3"):
                assert not np.allclose(stats[f"graph_convs_{i}"][norm]["mean"],
                                       variables["batch_stats"][f"graph_convs_{i}"][norm]["mean"])


@pytest.mark.parametrize("objective", ["mlip", "plain"])
def test_loss_and_parameter_gradient(pair, objective):
    """The step's own objective: under ``mlip`` the parameter gradient is
    grad-of-grad through the scan."""
    name, unrolled, scanned, variables, batch = pair
    if objective == "plain":  # the same stacks trained on their node head directly
        unrolled, batch = build(name, scan=False, mlip=False)
        scanned, _ = build(name, scan=True, mlip=False)
        variables = unrolled.init(jax.random.PRNGKey(0), batch, train=False)
        variables = seeded({"params": variables["params"],
                            "batch_stats": variables.get("batch_stats", {})})
    out = {}
    for model in (unrolled, scanned):
        make = energy_force_objective if objective == "mlip" else model_objective
        fn = make(model)

        def loss(params):
            tot, tasks, stats = fn(params, variables["batch_stats"], batch, batch,
                                   jax.random.PRNGKey(1))
            return tot, stats

        (tot, stats), grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(variables["params"])
        out[model.spec.scan_conv_layers] = (tot, grad, stats)
    assert jax.tree.structure(out[False]) == jax.tree.structure(out[True])
    # the seeded weights are large: a grad-of-grad's rounding reaches 1e-5 of a leaf
    close(out[False], out[True], (name, objective), rtol=5e-5)


def test_the_scan_composes_with_conv_checkpointing():
    unrolled, batch = build("gps_egnn", scan=False)
    scanned, _ = build("gps_egnn", scan=True)
    remat = scanned.clone(spec=scanned.spec.__class__(
        **{**scanned.spec.__dict__, "conv_checkpointing": True}))
    variables = seeded(dict(unrolled.init(jax.random.PRNGKey(0), batch, train=False)))
    want = jax.jit(make_energy_and_forces(unrolled))(variables, batch)
    got = jax.jit(make_energy_and_forces(remat))(variables, batch)
    close(want, got, "remat")


def test_dropout_under_the_scan_draws_a_mask_a_layer():
    model, batch = build("gps_egnn", scan=True, dropout=0.5)
    variables = dict(model.init(jax.random.PRNGKey(0), batch, train=False))
    run = lambda key: model.apply(variables, batch, train=True, mutable=["batch_stats"],
                                  rngs={"dropout": key})[0][0]
    a, b, c = run(jax.random.PRNGKey(1)), run(jax.random.PRNGKey(1)), run(jax.random.PRNGKey(2))
    assert np.all(np.isfinite(a)) and np.array_equal(a, b) and not np.allclose(a, c)


def test_what_the_scan_refuses():
    model, batch = build("gps_egnn", scan=True)
    variables = model.init(jax.random.PRNGKey(0), batch, train=False)
    with pytest.raises(ValueError, match="layer_hook"):
        model.apply(variables, batch, layer_hook=lambda inv, equiv: (inv, equiv))
    with pytest.raises(ValueError, match="only \\['batch_stats'\\]"):
        model.apply(variables, batch, train=True, mutable=["batch_stats", "intermediates"])
    # two layers, the second without a coordinate gate: nothing to scan
    model, batch = build("egnn", scan=True, layers=2)
    variables = model.init(jax.random.PRNGKey(0), batch, train=False)
    with pytest.raises(ValueError, match="no two consecutive conv blocks"):
        model.apply(variables, batch)
    with pytest.raises(ValueError, match="not parameter-homogeneous"):
        layer_scan.stack_layers(variables["params"], {}, 0, 2)


def test_a_stack_that_reads_every_layer_is_refused():
    from hydragnn_tpu.models.base import CONV_REGISTRY

    model, batch = build("egnn", scan=True)
    variables = model.init(jax.random.PRNGKey(0), batch, train=False)
    conv = CONV_REGISTRY["EGNN"]
    conv.collect_layer_outputs = True
    try:
        with pytest.raises(ValueError, match="collect_layer_outputs"):
            layer_scan.scanned_apply(model, variables, batch)
    finally:
        del conv.collect_layer_outputs


def test_a_method_call_and_an_unset_key_are_what_they_were():
    unrolled, batch = build("egnn", scan=False)
    scanned, _ = build("egnn", scan=True)
    variables = unrolled.init(jax.random.PRNGKey(0), batch, train=False)
    jaxpr = lambda m, **kw: str(jax.make_jaxpr(lambda v: m.apply(v, batch, **kw))(variables))
    assert "while" not in jaxpr(unrolled) and "scan" not in jaxpr(unrolled)
    assert "scan" in jaxpr(scanned)
    encode = type(scanned).encode
    assert jaxpr(scanned, method=encode) == jaxpr(unrolled, method=encode)


def test_kernel_rules_under_a_scan_and_a_second_differentiation(monkeypatch):
    """``ops/routing.py::saved``. The row-sum kernel and ``segment.gather`` are
    custom-VJP functions whose rules call each other and save their id array,
    an INPUT; jax 0.9.0 forwards such a residual by its index, and in a scan
    body under grad-of-grad hands ``bwd`` another operand. The rules save a
    copy, and the scan gives the unrolled loop's gradient; with the inputs
    themselves saved the same program does not trace (or, with operands of one
    type, would be silently wrong). If the last assertion fails, jax has
    mended the forwarding: save the inputs themselves again."""
    from hydragnn_tpu.graphs import segment
    from hydragnn_tpu.ops import routing

    monkeypatch.setenv("HYDRAGNN_FUSED_SCATTER", "1")  # the kernel, interpreted here
    n, e, c = 256, 1024, 128
    ids = jnp.asarray(np.sort(np.random.default_rng(0).integers(0, n, e)).astype(np.int32))
    x = 0.1 * jnp.ones((n, c))
    ws = jnp.linspace(0.5, 1.5, 3 * c).reshape(3, c)

    def layer(h, w):
        return jnp.tanh(segment.segment_sum(segment.gather(h, ids, fits=True) * w, ids, n,
                                            fits=True))

    def energy(x, ws, scan):
        if scan:
            return jax.lax.scan(lambda h, w: (layer(h, w), None), x, ws)[0].sum()
        for w in ws:
            x = layer(x, w)
        return x.sum()

    def force_loss_gradient(scan):
        return jax.jit(jax.grad(
            lambda ws: (jax.grad(energy)(x, ws, scan) ** 2).sum()))(ws)

    want = force_loss_gradient(scan=False)
    np.testing.assert_allclose(force_loss_gradient(scan=True), want, rtol=0,
                               atol=1e-4 * np.abs(want).max())
    monkeypatch.setattr(routing, "saved", lambda residuals: residuals)
    with pytest.raises((TypeError, ValueError)):
        force_loss_gradient(scan=True)
