"""Config system: the reference's JSON schema, validated and augmented.

Keeps the ORNL/HydraGNN JSON config schema verbatim (sections ``Verbosity`` /
``Dataset`` / ``NeuralNetwork.{Architecture,Variables_of_interest,Training}`` /
``Visualization`` — see reference ``tests/inputs/ci.json`` and
``README.md:140-195``) and reproduces the derivation rules of ``update_config``
(reference ``hydragnn/utils/input_config_parsing/config_utils.py:26-163``):
default filling, multibranch head normalization, output-dim extraction from
data, PNA degree histograms, MACE average neighbor counts, edge-dim rules.

On top of the raw dict (which remains the source of truth and what
``save_config`` writes), ``ModelSpec.from_config`` extracts a frozen, typed
view consumed by the model factory — the TPU build's replacement for threading
a mutable dict through every constructor.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from copy import deepcopy
from typing import Any, Sequence

import numpy as np

# The top-level sections of the repo's JSON config schema — THE single
# definition (block-or-full-config sniffers in serve/server.py and
# telemetry/config.py import it, so a new section added here reaches every
# consumer instead of drifting across hand-copied sets).
CONFIG_SECTIONS = frozenset(
    {"Verbosity", "Dataset", "NeuralNetwork", "Visualization", "Serving",
     "MD", "Telemetry", "Screening"}
)

# Architectures grouped by capability (reference ``config_utils.py:64,179-206``).
PNA_MODELS = ("PNA", "PNAPlus", "PNAEq")
EDGE_MODELS = (
    "GAT", "PNA", "PNAPlus", "PAINN", "PNAEq", "CGCNN", "SchNet", "EGNN",
    "DimeNet", "MACE",
)
ALL_MPNN_TYPES = (
    "GIN", "SAGE", "GAT", "MFC", "CGCNN", "PNA", "PNAPlus", "SchNet",
    "DimeNet", "EGNN", "PAINN", "PNAEq", "MACE",
)

# Architecture keys defaulted to None when absent (``config_utils.py:95-128``).
_ARCH_NONE_DEFAULTS = (
    "radius", "radial_type", "distance_transform", "num_gaussians",
    "num_filters", "envelope_exponent", "num_after_skip", "num_before_skip",
    "num_output_layers",
    "basis_emb_size", "int_emb_size", "out_emb_size", "num_radial",
    "num_spherical", "correlation", "max_ell", "node_max_ell", "initial_bias",
    "equivariance",
)


def load_config(source: str | dict) -> dict:
    """Accept a JSON file path or an already-parsed dict (the reference's
    ``run_training`` singledispatch, ``run_training.py:59-74``)."""
    if isinstance(source, dict):
        return deepcopy(source)
    with open(source) as f:
        return json.load(f)


def merge_config(a: dict, b: dict) -> dict:
    """Deep merge ``b`` over ``a`` (reference ``config_utils.py:388-396``)."""
    result = deepcopy(a)
    for bk, bv in b.items():
        av = result.get(bk)
        if isinstance(av, dict) and isinstance(bv, dict):
            result[bk] = merge_config(av, bv)
        else:
            result[bk] = deepcopy(bv)
    return result


def update_multibranch_heads(output_heads: dict) -> dict:
    """Normalize legacy single-branch head configs to the multibranch form
    (reference ``utils/model/model.py:314-349``): each head family becomes a
    list of ``{"type": "branch-N", "architecture": {...}}`` dicts."""
    updated = dict(output_heads)
    for name, val in output_heads.items():
        if isinstance(val, list):
            for branch in val:
                if not (isinstance(branch, dict) and "type" in branch and "architecture" in branch):
                    raise ValueError(
                        f"output_heads['{name}'] does not contain proper branch config: {val}"
                    )
        elif isinstance(val, dict):
            updated[name] = [{"type": "branch-0", "architecture": val}]
        else:
            raise ValueError("Unknown output_heads config!")
    return updated


def _degree_histogram(samples) -> list[int]:
    """In-degree histogram over the training set — PNA's ``deg`` input
    (reference ``gather_deg``, ``graph_samples_checks_and_updates.py:526-601``)."""
    per_sample = []
    for s in samples:
        deg = np.bincount(np.asarray(s.receivers), minlength=s.num_nodes)[: s.num_nodes]
        per_sample.append(np.bincount(deg))
    if not per_sample:
        return [0]
    width = max(h.shape[0] for h in per_sample)
    hist = np.zeros(width, np.int64)
    for h in per_sample:
        hist[: h.shape[0]] += h
    return hist.tolist()


def _avg_num_neighbors(samples) -> float:
    tot_edges = sum(s.num_edges for s in samples)
    tot_nodes = sum(s.num_nodes for s in samples)
    return float(tot_edges) / max(tot_nodes, 1)


def update_config(config: dict, train_samples, val_samples=None, test_samples=None) -> dict:
    """Fill defaults and derive data-dependent architecture fields.

    Mirrors reference ``update_config`` (``config_utils.py:26-163``) with the
    dataset represented as a sequence of ``GraphSample``s instead of torch
    DataLoaders. The ``y_loc`` offset machinery is gone: targets are columnar
    (see ``hydragnn_tpu.graphs.graph``), so output dims come straight from the
    ``Dataset`` feature dims selected by ``output_index``.
    """
    config = deepcopy(config)
    nn = config.setdefault("NeuralNetwork", {})
    arch = nn.setdefault("Architecture", {})
    voi = nn.setdefault("Variables_of_interest", {})
    training = nn.setdefault("Training", {})

    # elastic data plane (datasets/sharded.py): the Dataset.store block's
    # defaults ARE the StoreConfig dataclass field defaults — same
    # single-source pattern as Training.resilience below. run_training
    # applies the filled block to a ShardedStore passed as the dataset;
    # HYDRAGNN_REPLICATION / HYDRAGNN_PEER_TIMEOUT override at the store.
    ds_cfg = config.setdefault("Dataset", {})
    store_cfg = ds_cfg.setdefault("store", {})
    if not isinstance(store_cfg, dict):
        raise ValueError(
            f"Dataset.store must be a dict, got {type(store_cfg).__name__}"
        )
    from ..datasets.sharded import store_config_defaults

    for key, val in store_config_defaults().items():
        store_cfg.setdefault(key, val)

    # serving tier (hydragnn_tpu.serve): the top-level Serving block's
    # defaults ARE the ServingConfig dataclass field defaults (same
    # single-source pattern as Dataset.store above); HYDRAGNN_SERVE_* env
    # flags override at server construction. Validated here so a typo'd
    # serving deployment fails at config load, not at first request.
    serving_cfg = config.setdefault("Serving", {})
    if not isinstance(serving_cfg, dict):
        raise ValueError(
            f"Serving must be a dict, got {type(serving_cfg).__name__}"
        )
    from ..serve.server import ServingConfig, serving_config_defaults

    serving_defaults = serving_config_defaults()
    unknown = set(serving_cfg) - set(serving_defaults)
    if unknown:
        raise ValueError(
            f"Unknown Serving key(s) {sorted(unknown)}; known: "
            f"{sorted(serving_defaults)}"
        )
    # nested Serving.fleet block (serve/fleet): fill its keys from the
    # FleetConfig dataclass defaults BEFORE the flat setdefault loop, so a
    # partial fleet block keeps the caller's keys and gains the rest
    fleet_cfg = serving_cfg.setdefault("fleet", {})
    if not isinstance(fleet_cfg, dict):
        raise ValueError(
            f"Serving.fleet must be a dict, got {type(fleet_cfg).__name__}"
        )
    from ..serve.fleet.config import fleet_config_defaults

    # unknown-key rejection lives in ServingConfig.validate() below (the
    # one implementation); unknown keys survive this back-fill untouched
    # and raise there
    for key, val in fleet_config_defaults().items():
        filled = fleet_cfg.setdefault(key, val)
        # one level deeper for the control-plane sub-blocks
        # (Serving.fleet.autoscale / Serving.fleet.rollout): a partial
        # sub-block keeps the caller's keys and gains the rest
        if isinstance(val, dict) and isinstance(filled, dict) and filled is not val:
            for sub_key, sub_val in val.items():
                filled.setdefault(sub_key, sub_val)
    for key, val in serving_defaults.items():
        serving_cfg.setdefault(key, val)
    # one range-check implementation; also validates the fleet block
    # through FleetConfig
    ServingConfig(**serving_cfg).validate()

    # on-device MD (hydragnn_tpu.md): the top-level MD block's defaults ARE
    # the MDConfig dataclass field defaults (same single-source pattern);
    # HYDRAGNN_FUSED_CELL_LIST overrides fused_cell_list at build time.
    md_cfg = config.setdefault("MD", {})
    if not isinstance(md_cfg, dict):
        raise ValueError(f"MD must be a dict, got {type(md_cfg).__name__}")
    from ..md import MDConfig, md_config_defaults

    md_defaults = md_config_defaults()
    unknown_md = set(md_cfg) - set(md_defaults)
    if unknown_md:
        raise ValueError(
            f"Unknown MD key(s) {sorted(unknown_md)}; known: "
            f"{sorted(md_defaults)}"
        )
    for key, val in md_defaults.items():
        md_cfg.setdefault(key, val)
    MDConfig(**md_cfg).validate()  # one range-check implementation

    # telemetry plane (hydragnn_tpu.telemetry): the top-level Telemetry
    # block's defaults ARE the TelemetryConfig dataclass field defaults
    # (same single-source pattern); HYDRAGNN_TELEMETRY /
    # HYDRAGNN_TRACE_EVENTS env flags win at apply time (run_training folds
    # them via TelemetryConfig.apply_env).
    tel_cfg = config.setdefault("Telemetry", {})
    if not isinstance(tel_cfg, dict):
        raise ValueError(
            f"Telemetry must be a dict, got {type(tel_cfg).__name__}"
        )
    from ..telemetry import TelemetryConfig, telemetry_config_defaults

    tel_defaults = telemetry_config_defaults()
    unknown_tel = set(tel_cfg) - set(tel_defaults)
    if unknown_tel:
        raise ValueError(
            f"Unknown Telemetry key(s) {sorted(unknown_tel)}; known: "
            f"{sorted(tel_defaults)}"
        )
    for key, val in tel_defaults.items():
        tel_cfg.setdefault(key, val)
    TelemetryConfig(**tel_cfg).validate()  # one range-check implementation

    # bulk screening (hydragnn_tpu.screen): the top-level Screening block's
    # defaults ARE the ScreeningConfig dataclass field defaults (same
    # single-source pattern); HYDRAGNN_SCREEN_TOPK / HYDRAGNN_SCREEN_PREFETCH
    # env flags win at engine construction (ScreeningConfig.apply_env).
    screen_cfg = config.setdefault("Screening", {})
    if not isinstance(screen_cfg, dict):
        raise ValueError(
            f"Screening must be a dict, got {type(screen_cfg).__name__}"
        )
    from ..screen import ScreeningConfig, screening_config_defaults

    screen_defaults = screening_config_defaults()
    unknown_screen = set(screen_cfg) - set(screen_defaults)
    if unknown_screen:
        raise ValueError(
            f"Unknown Screening key(s) {sorted(unknown_screen)}; known: "
            f"{sorted(screen_defaults)}"
        )
    for key, val in screen_defaults.items():
        screen_cfg.setdefault(key, val)
    ScreeningConfig(**screen_cfg).validate()  # one range-check impl

    # --- GPS / encoding defaults (reference :40-48) ---
    arch.setdefault("global_attn_engine", None)
    arch.setdefault("global_attn_type", None)
    arch.setdefault("global_attn_heads", 0)
    arch.setdefault("pe_dim", 0)
    # Static per-graph width for dense-block attention (the reference's
    # to_dense_batch N_max, globalAtt/gps.py:126-133, made compile-time):
    # 8-aligned; graphs bigger than this fall back in-program to flat masked
    # attention inside GPSConv.
    if arch.get("global_attn_engine") and not arch.get("max_graph_nodes"):
        max_n = max((s.num_nodes for s in train_samples), default=0)
        arch["max_graph_nodes"] = int(math.ceil(max(max_n, 1) / 8) * 8)
    else:
        arch.setdefault("max_graph_nodes", None)

    # accepted-but-subsumed sections warn instead of silently vanishing
    if nn.get("ds_config"):
        import warnings

        warnings.warn(
            "NeuralNetwork.ds_config (DeepSpeed) is subsumed by XLA SPMD "
            "sharding on TPU: ZeRO-1 optimizer sharding is automatic with "
            "sharded params, and HYDRAGNN_USE_FSDP=1 gives ZeRO-3-style "
            "parameter sharding. The ds_config section is ignored."
        )

    # --- head normalization (reference :50-53) ---
    arch["output_heads"] = update_multibranch_heads(arch.get("output_heads", {}))

    # --- output dims/types (reference update_config_NN_outputs :227-268) ---
    output_type = list(voi.get("type", []))
    output_index = list(voi.get("output_index", []))
    if "output_dim" in voi and voi["output_dim"]:
        dims_list = list(voi["output_dim"])
    else:
        dims_list = []
        for ihead, otype in enumerate(output_type):
            feats = (
                config["Dataset"]["graph_features"]
                if otype == "graph"
                else config["Dataset"]["node_features"]
            )
            dims_list.append(int(feats["dim"][output_index[ihead]]))
    arch["output_dim"] = dims_list
    arch["output_type"] = output_type
    first = train_samples[0] if len(train_samples) else None
    arch["num_nodes"] = int(first.num_nodes) if first is not None else None
    graph_size_variable = len({s.num_nodes for s in train_samples}) > 1
    from ..utils import flags

    env_var = flags.get(flags.USE_VARIABLE_GRAPH_SIZE)
    if env_var is not None:
        graph_size_variable = env_var
    arch["graph_size_variable"] = graph_size_variable
    if graph_size_variable:
        for branch in arch["output_heads"].get("node", []):
            if branch["architecture"].get("type") == "mlp_per_node":
                raise ValueError(
                    '"mlp_per_node" is not allowed for variable graph size; use "mlp" or "conv"'
                )

    # --- input dim (reference :61-63) ---
    arch["input_dim"] = len(voi.get("input_node_features", []))

    # --- PNA degree histogram (reference :64-74) ---
    if arch.get("mpnn_type") in PNA_MODELS:
        if "pna_deg" not in arch or arch["pna_deg"] is None:
            arch["pna_deg"] = _degree_histogram(train_samples)
        arch["max_neighbours"] = len(arch["pna_deg"]) - 1
    else:
        arch.setdefault("pna_deg", None)

    # --- CGCNN hidden dim rule (reference :76-83) ---
    if arch.get("mpnn_type") == "CGCNN" and not arch.get("global_attn_engine"):
        arch["hidden_dim"] = arch["input_dim"]

    # --- MACE avg neighbors (reference :85-93) ---
    if arch.get("mpnn_type") == "MACE":
        if "avg_num_neighbors" not in arch or arch["avg_num_neighbors"] is None:
            arch["avg_num_neighbors"] = _avg_num_neighbors(train_samples)
    else:
        arch.setdefault("avg_num_neighbors", None)

    for key in _ARCH_NONE_DEFAULTS:
        arch.setdefault(key, None)
    arch.setdefault("enable_interatomic_potential", False)

    # --- edge dim rules (reference update_config_edge_dim :179-206) ---
    arch["edge_dim"] = None
    if arch.get("edge_features"):
        if arch["mpnn_type"] not in EDGE_MODELS:
            raise ValueError(
                f"Edge features can only be used with {', '.join(EDGE_MODELS)}."
            )
        if arch.get("enable_interatomic_potential"):
            raise ValueError(
                "Edge features cannot be used with interatomic potentials."
            )
        arch["edge_dim"] = len(arch["edge_features"])
    elif arch.get("mpnn_type") == "CGCNN":
        arch["edge_dim"] = 0

    arch.setdefault("freeze_conv_layers", False)
    arch.setdefault("activation_function", "relu")
    arch.setdefault("SyncBatchNorm", False)
    # halo-exchange graph partitioning (parallel/halo.py): the
    # Architecture.halo block's defaults ARE the HaloConfig dataclass field
    # defaults (same single-source pattern); HYDRAGNN_HALO overrides
    # `enabled` at routing time.
    halo_cfg = arch.setdefault("halo", {})
    if not isinstance(halo_cfg, dict):
        raise ValueError(
            f"Architecture.halo must be a dict, got {type(halo_cfg).__name__}"
        )
    from ..parallel.halo import HaloConfig, halo_config_defaults

    halo_defaults = halo_config_defaults()
    unknown_halo = set(halo_cfg) - set(halo_defaults)
    if unknown_halo:
        raise ValueError(
            f"Unknown Architecture.halo key(s) {sorted(unknown_halo)}; "
            f"known: {sorted(halo_defaults)}"
        )
    for key, val in halo_defaults.items():
        halo_cfg.setdefault(key, val)
    HaloConfig(**halo_cfg).validate()  # one range-check implementation
    training.setdefault("conv_checkpointing", False)
    # run the shape-homogeneous conv blocks as one lax.scan over their stacked
    # subtrees (models/layer_scan.py): one layer's HLO, not num_conv_layers
    training.setdefault("scan_conv_layers", False)
    # K train steps per device dispatch (train/superstep.py); env override
    # HYDRAGNN_SUPERSTEP wins at loop time
    training.setdefault("steps_per_dispatch", 1)
    # population training (train/population.py): N ensemble members / HPO
    # trials vmapped into one jitted program. size 0/1 = disabled (env
    # override HYDRAGNN_POPULATION wins); the per-member lists are optional
    # and must be length `size` when given (seeds default to range(size) —
    # a deep ensemble wants distinct inits; learning_rates/weight_decays/
    # task_weights default to the shared Optimizer/Architecture values).
    pop_cfg = training.setdefault("population", {})
    if not isinstance(pop_cfg, dict):
        raise ValueError(
            f"Training.population must be a dict, got {type(pop_cfg).__name__}"
        )
    pop_cfg.setdefault("size", 0)
    pop_cfg.setdefault("seeds", None)
    pop_cfg.setdefault("learning_rates", None)
    pop_cfg.setdefault("weight_decays", None)
    pop_cfg.setdefault("task_weights", None)
    for _k in ("seeds", "learning_rates", "weight_decays", "task_weights"):
        vals = pop_cfg[_k]
        if vals is not None and len(vals) != int(pop_cfg["size"] or 0):
            raise ValueError(
                f"Training.population.{_k} has {len(vals)} entries for "
                f"size={pop_cfg['size']}"
            )
    # fault tolerance (hydragnn_tpu.resilience): non-finite step guard with
    # rollback escalation, preemption checkpointing, hung-dispatch watchdog
    res_cfg = training.setdefault("resilience", {})
    if not isinstance(res_cfg, dict):
        raise ValueError(
            f"Training.resilience must be a dict, got {type(res_cfg).__name__}"
        )
    # "auto" = guard reduced-precision training (bf16/fp16, where non-finite
    # steps are routine) and leave fp32 opt-in: the guard's finiteness
    # probe + pytree select adds an extra XLA compile of the step program,
    # which fp32 runs that practically never diverge shouldn't pay for
    res_cfg.setdefault("nonfinite_guard", "auto")
    from ..resilience import config_defaults

    for key, val in config_defaults().items():
        res_cfg.setdefault(key, val)
    training.setdefault("loss_function_type", "mse")
    # precision is validated against the step builders' known dtype set (plus
    # the backend-resolved "auto" fast path) so a typo'd value fails at
    # config load, not 40 s into the first TPU compile; HYDRAGNN_PRECISION
    # overrides at step-build time (train.step.resolve_training_precision)
    training.setdefault("precision", "fp32")
    from ..train.step import KNOWN_PRECISIONS

    if str(training["precision"]) not in KNOWN_PRECISIONS:
        raise ValueError(
            f"Training.precision {training['precision']!r} not one of "
            f"{sorted(KNOWN_PRECISIONS)}"
        )
    # static loss scale for fp16-class compute (train/step.py): 0/1 = off
    # (the historical byte-identical program); validated here so a negative
    # or non-numeric scale fails at load
    training.setdefault("loss_scale", 0)
    if (
        isinstance(training["loss_scale"], bool)
        or not isinstance(training["loss_scale"], (int, float))
        # json.loads admits NaN/Infinity literals; a non-finite scale would
        # NaN every gradient at step time instead of failing here
        or not math.isfinite(float(training["loss_scale"]))
        or float(training["loss_scale"]) < 0
    ):
        raise ValueError(
            f"Training.loss_scale must be a finite number >= 0 (0/1 "
            f"disables), got {training['loss_scale']!r}"
        )
    training.setdefault("batch_size", 32)
    training.setdefault("Optimizer", {"type": "AdamW", "learning_rate": 1e-3})
    # per-member weight decays need the decay INJECTED as a runtime
    # hyperparameter, which select_optimizer only does for an explicit
    # Optimizer.weight_decay (implicit decay stays a baked constant so the
    # opt_state pytree — and every pre-existing checkpoint — keeps its
    # historical structure): auto-fill the optax default when a population
    # asks for per-member decays. Gated on the RESOLVED size (env wins):
    # HYDRAGNN_POPULATION=0 must give the plain single-state run its
    # historical pytree back, or disabling population mode would break the
    # very checkpoint resume the explicit-only rule protects.
    if pop_cfg.get("weight_decays") is not None:
        from ..train.population import resolve_population_size

        if resolve_population_size(training) > 1:
            from ..train.optimizer import ensure_injected_weight_decay

            ensure_injected_weight_decay(training["Optimizer"])
    voi.setdefault("denormalize_output", False)

    return config


def get_log_name_config(config: dict) -> str:
    """Run-name string (reference ``config_utils.py:322-357``)."""
    arch = config["NeuralNetwork"]["Architecture"]
    training = config["NeuralNetwork"]["Training"]
    name = config["Dataset"]["name"]
    trimmed = name[: name.rfind("_")] if name.rfind("_") > 0 else name
    return (
        f"{arch['mpnn_type']}-r-{arch.get('radius')}-ncl-{arch['num_conv_layers']}"
        f"-hd-{arch['hidden_dim']}-ne-{training['num_epoch']}"
        f"-lr-{training['Optimizer']['learning_rate']}-bs-{training['batch_size']}"
        f"-data-{trimmed}"
        "-node_ft-"
        + "".join(
            str(x)
            for x in config["NeuralNetwork"]["Variables_of_interest"]["input_node_features"]
        )
        + "-task_weights-"
        + "".join(f"{w}-" for w in arch["task_weights"])
    )


def save_config(config: dict, log_name: str, path: str = "./logs/") -> None:
    """Persist the augmented config next to the run logs (reference
    ``config_utils.py:360-366``); caller gates on process index 0."""
    fname = os.path.join(path, log_name, "config.json")
    os.makedirs(os.path.dirname(fname), exist_ok=True)
    with open(fname, "w") as f:
        json.dump(config, f, indent=4)


# ---------------------------------------------------------------------------
# Typed view for the model factory
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeadBranchSpec:
    branch: str  # "branch-0", "branch-1", ...
    num_sharedlayers: int = 0
    dim_sharedlayers: int = 0
    num_headlayers: int = 1
    dim_headlayers: tuple[int, ...] = ()
    node_type: str | None = None  # "mlp" | "mlp_per_node" | "conv" for node heads


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Everything the model factory needs, extracted from the augmented dict."""

    mpnn_type: str
    input_dim: int
    hidden_dim: int
    num_conv_layers: int
    output_dim: tuple[int, ...]
    output_type: tuple[str, ...]  # "graph" | "node" per head
    graph_heads: tuple[HeadBranchSpec, ...]
    node_heads: tuple[HeadBranchSpec, ...]
    task_weights: tuple[float, ...]
    activation: str = "relu"
    loss_type: str = "mse"
    graph_pooling: str = "mean"
    dropout: float = 0.25
    # geometry / radial
    radius: float | None = None
    max_neighbours: int | None = None
    radial_type: str | None = None
    num_gaussians: int | None = None
    num_filters: int | None = None
    num_radial: int | None = None
    num_spherical: int | None = None
    envelope_exponent: int | None = None
    basis_emb_size: int | None = None
    int_emb_size: int | None = None
    out_emb_size: int | None = None
    num_before_skip: int | None = None
    num_after_skip: int | None = None
    num_output_layers: int | None = None  # dense layers of DimeNet's output block (1)
    distance_transform: str | None = None
    # equivariance / MACE
    equivariance: bool | None = None
    max_ell: int | None = None
    node_max_ell: int | None = None
    correlation: Any = None
    avg_num_neighbors: float | None = None
    # data-derived
    pna_deg: tuple[int, ...] | None = None
    num_nodes: int | None = None
    edge_dim: int | None = None
    # global attention
    global_attn_engine: str | None = None
    global_attn_type: str | None = None
    global_attn_heads: int = 0
    max_graph_nodes: int | None = None
    pe_dim: int = 0
    # conditioning / misc
    use_graph_attr_conditioning: bool = False
    graph_attr_conditioning_mode: str = "concat_node"
    enable_interatomic_potential: bool = False
    energy_weight: float = 0.0
    energy_peratom_weight: float = 0.0
    force_weight: float = 0.0
    freeze_conv_layers: bool = False
    initial_bias: float | None = None
    sync_batch_norm: bool = False
    # mesh axis name feature-norm statistics must psum over — set ONLY by the
    # halo-partitioned step factory (dataclasses.replace), never from config:
    # a partitioned node set has no correct per-device statistics
    bn_sync_axis: str | None = None
    conv_checkpointing: bool = False
    scan_conv_layers: bool = False
    var_output: bool = False
    graph_size_variable: bool = False

    @property
    def num_heads(self) -> int:
        return len(self.output_dim)

    @property
    def num_branches(self) -> int:
        return max(len(self.graph_heads), len(self.node_heads), 1)

    @property
    def graph_y_dim(self) -> int:
        return sum(
            (d * (2 if self.var_output else 1))
            for d, t in zip(self.output_dim, self.output_type)
            if t == "graph"
        )

    @staticmethod
    def from_config(config: dict) -> "ModelSpec":
        arch = config["NeuralNetwork"]["Architecture"]
        training = config["NeuralNetwork"].get("Training", {})
        heads_cfg = arch.get("output_heads", {})

        def branches(family: str) -> tuple[HeadBranchSpec, ...]:
            out = []
            for b in heads_cfg.get(family, []):
                a = b["architecture"]
                dims = a.get("dim_headlayers", [])
                out.append(
                    HeadBranchSpec(
                        branch=b["type"],
                        num_sharedlayers=int(a.get("num_sharedlayers", 0)),
                        dim_sharedlayers=int(a.get("dim_sharedlayers", 0)),
                        num_headlayers=int(a.get("num_headlayers", len(dims))),
                        dim_headlayers=tuple(int(d) for d in dims),
                        node_type=a.get("type"),
                    )
                )
            return tuple(out)

        task_weights = arch.get("task_weights") or [1.0] * len(arch["output_dim"])
        wsum = sum(abs(w) for w in task_weights)
        task_weights = tuple(w / wsum for w in task_weights)  # Base.py:121-132

        return ModelSpec(
            mpnn_type=arch["mpnn_type"],
            input_dim=int(arch["input_dim"]),
            hidden_dim=int(arch["hidden_dim"]),
            num_conv_layers=int(arch["num_conv_layers"]),
            output_dim=tuple(int(d) for d in arch["output_dim"]),
            output_type=tuple(arch["output_type"]),
            graph_heads=branches("graph"),
            node_heads=branches("node"),
            task_weights=task_weights,
            activation=arch.get("activation_function", "relu"),
            loss_type=training.get("loss_function_type", "mse"),
            graph_pooling=arch.get("graph_pooling", "mean"),
            dropout=float(arch.get("dropout", 0.25)),
            radius=arch.get("radius"),
            max_neighbours=arch.get("max_neighbours"),
            radial_type=arch.get("radial_type"),
            num_gaussians=arch.get("num_gaussians"),
            num_filters=arch.get("num_filters"),
            num_radial=arch.get("num_radial"),
            num_spherical=arch.get("num_spherical"),
            envelope_exponent=arch.get("envelope_exponent"),
            basis_emb_size=arch.get("basis_emb_size"),
            int_emb_size=arch.get("int_emb_size"),
            out_emb_size=arch.get("out_emb_size"),
            num_before_skip=arch.get("num_before_skip"),
            num_after_skip=arch.get("num_after_skip"),
            num_output_layers=arch.get("num_output_layers"),
            distance_transform=arch.get("distance_transform"),
            equivariance=arch.get("equivariance"),
            max_ell=arch.get("max_ell"),
            node_max_ell=arch.get("node_max_ell"),
            correlation=arch.get("correlation"),
            avg_num_neighbors=arch.get("avg_num_neighbors"),
            pna_deg=tuple(arch["pna_deg"]) if arch.get("pna_deg") else None,
            num_nodes=arch.get("num_nodes"),
            edge_dim=arch.get("edge_dim"),
            global_attn_engine=arch.get("global_attn_engine") or None,
            global_attn_type=arch.get("global_attn_type") or None,
            global_attn_heads=int(arch.get("global_attn_heads") or 0),
            max_graph_nodes=arch.get("max_graph_nodes") or None,
            pe_dim=int(arch.get("pe_dim") or 0),
            use_graph_attr_conditioning=bool(arch.get("use_graph_attr_conditioning", False)),
            graph_attr_conditioning_mode=arch.get("graph_attr_conditioning_mode", "concat_node"),
            enable_interatomic_potential=bool(arch.get("enable_interatomic_potential", False)),
            energy_weight=float(arch.get("energy_weight", 0.0)),
            energy_peratom_weight=float(arch.get("energy_peratom_weight", 0.0)),
            force_weight=float(arch.get("force_weight", 0.0)),
            freeze_conv_layers=bool(arch.get("freeze_conv_layers", False)),
            initial_bias=arch.get("initial_bias"),
            # reference spelling: Architecture.SyncBatchNorm (run_training.py:108)
            sync_batch_norm=bool(arch.get("SyncBatchNorm", False)),
            conv_checkpointing=bool(training.get("conv_checkpointing", False)),
            scan_conv_layers=bool(training.get("scan_conv_layers", False)),
            var_output=training.get("loss_function_type") == "GaussianNLLLoss",
            graph_size_variable=bool(arch.get("graph_size_variable", False)),
        )
