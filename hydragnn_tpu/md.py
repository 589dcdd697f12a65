"""On-device molecular dynamics with MLIP models.

The reference's neighbor search (vesin, ``graph_samples_checks_and_updates
.py:170-176``) is HOST-side: an MD loop driven by its models pays a
device->host->device round trip per step to rebuild the graph. This module
keeps the whole MD step on the TPU:

* ``dynamic_radius_graph`` — a jit-able radius graph with STATIC output
  shapes: the O(N^2) minimum-image distance matrix is one MXU-friendly
  matmul-shaped op, and the edge list lands in fixed ``[max_edges]`` arrays
  via ``jnp.nonzero(..., size=...)`` (padded entries masked). Fastest for
  small systems (10^2-10^3 atoms) because it never leaves the device.
* ``binned_radius_graph`` + ``plan_cell_grid`` — the on-device cell list
  (SURVEY S2.9's vesin role): O(N x 27 x capacity) memory, same edge/shift
  semantics as the dense build, 10^4-10^5 atoms in bounded memory. The
  integrators pick it automatically (``neighbor="auto"``) at >= 512 atoms
  when the periodic cell admits a 3x3x3+ grid; beyond single-chip HBM,
  shard atoms over the mesh first.
* ``velocity_verlet`` / ``make_md_step`` — the standard integrator with
  forces from ``jax.grad`` of any energy function (e.g. an MLIP model's
  energy head), one ``lax.scan`` per trajectory segment: graph rebuild,
  force evaluation, and integration all inside a single compiled program.

This exceeds the reference (which has no on-device MD path) while reusing
its semantics: edges are directed pairs within ``cutoff`` under minimum-
image PBC, matching ``graphs.radius.radius_graph`` (tested for parity).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def _geom_dot(a: Array, b: Array) -> Array:
    """``a @ b`` for coordinates against a cell matrix, at full fp32
    precision. A TPU multiplies fp32 matrices in ONE bf16 pass by default —
    8 mantissa bits: a 50 A cell edge is then off by ~0.1 A, which moved
    minimum-image shifts (and, in ``_wrap_positions``, the positions
    themselves) by that much on the chip. These products are [n, 3] x [3, 3]:
    the six-pass mode costs nothing."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _inv3(cell: Array) -> Array:
    """Inverse of a 3x3 cell matrix from cross products — elementwise fp32
    arithmetic only, for the same reason as :func:`_geom_dot` (an LU-based
    inverse multiplies on the MXU)."""
    a, b, c = cell[0], cell[1], cell[2]
    bc, ca, ab = jnp.cross(b, c), jnp.cross(c, a), jnp.cross(a, b)
    return jnp.stack([bc, ca, ab], axis=1) / jnp.sum(a * bc)


@dataclasses.dataclass
class MDConfig:
    """The top-level ``MD`` config block — these field defaults ARE the
    schema defaults (single-source, the ``ServingConfig``/``StoreConfig``
    pattern; ``config/schema.py`` validates the block against them).
    ``HYDRAGNN_FUSED_CELL_LIST`` overrides ``fused_cell_list`` at build
    time (``binned_radius_graph``)."""

    neighbor: str = "auto"          # dense | cell | auto (see make_md_step)
    capacity_factor: float = 2.5    # plan_cell_grid per-cell slot headroom
    fused_cell_list: bool | None = None  # None = flag/backend auto

    @staticmethod
    def from_config(config: dict | None) -> "MDConfig":
        """Read a full config dict's ``MD`` block (absent = defaults)."""
        block = (config or {}).get("MD") or {}
        unknown = set(block) - set(md_config_defaults())
        if unknown:
            raise ValueError(
                f"Unknown MD key(s) {sorted(unknown)}; known: "
                f"{sorted(md_config_defaults())}"
            )
        return MDConfig(**block).validate()

    def validate(self) -> "MDConfig":
        if self.neighbor not in ("auto", "cell", "dense"):
            raise ValueError(
                f"MD.neighbor must be 'auto', 'cell', or 'dense', got "
                f"{self.neighbor!r}"
            )
        if float(self.capacity_factor) <= 1.0:
            raise ValueError(
                "MD.capacity_factor must be > 1 (per-cell slot headroom), "
                f"got {self.capacity_factor}"
            )
        if self.fused_cell_list is not None and not isinstance(
            self.fused_cell_list, bool
        ):
            raise ValueError(
                "MD.fused_cell_list must be true/false/null, got "
                f"{self.fused_cell_list!r}"
            )
        return self

    def step_kwargs(self) -> dict:
        """Kwargs for ``make_md_step`` / ``make_langevin_step`` / ``run_md``."""
        return {
            "neighbor": self.neighbor,
            "fused": self.fused_cell_list,
            "capacity_factor": float(self.capacity_factor),
        }


def md_config_defaults() -> dict:
    return dataclasses.asdict(MDConfig())


def dynamic_radius_graph(
    pos: Array,
    cutoff: float,
    max_edges: int,
    cell: Array | None = None,
    pbc: Array | None = None,
    pad_id: int = 0,
):
    """Jit-able directed radius graph with static shapes.

    Returns ``(senders, receivers, shifts, edge_mask, n_edges)``:
    ``senders``/``receivers`` are ``[max_edges]`` int32 (padded entries
    point at ``pad_id`` with ``edge_mask`` 0 — pass the batch's reserved
    dummy-node index when feeding a model, so unmasked mean/count
    aggregations never see pad edges at a real atom), ``shifts`` the Cartesian
    minimum-image shift vectors (``pos[r] - pos[s] + shift`` is the edge
    vector, the ``radius_graph`` convention), and ``n_edges`` the true edge
    count — callers must check ``n_edges <= max_edges`` (an overflow keeps
    the nearest-by-index prefix and flags itself via ``n_edges``).

    PBC uses single minimum image per pair (one image per neighbor), valid
    while ``cutoff < half the smallest cell height`` — the standard MD
    regime; multi-image edges need the host-side builder."""
    n = pos.shape[0]
    if n * n >= 2**31:
        # jnp.nonzero flat indices are int32; n^2 past that silently wraps
        # into wrong senders/receivers (round-4 advisor finding)
        raise ValueError(
            f"dense neighbor build overflows int32 flat indices at n={n}; "
            "use the binned cell list (binned_radius_graph / neighbor='cell')"
        )
    disp = pos[None, :, :] - pos[:, None, :]  # [s, r, 3] = pos[r] - pos[s]
    shift = jnp.zeros_like(disp)
    # periodic only when BOTH cell and pbc are given — the host builder's
    # semantics (graphs/radius.py treats pbc=None as open space)
    if cell is not None and pbc is not None:
        cell = jnp.asarray(cell, pos.dtype).reshape(3, 3)
        frac = _geom_dot(disp, _inv3(cell))
        wrap = jnp.round(frac) * jnp.asarray(pbc, pos.dtype).reshape(3)
        shift = -_geom_dot(wrap, cell)
        disp = disp + shift
    d2 = jnp.sum(disp * disp, axis=-1)
    within = (d2 <= cutoff * cutoff) & ~jnp.eye(n, dtype=bool)
    n_edges = within.sum()
    flat_idx = jnp.nonzero(
        within.reshape(-1), size=max_edges, fill_value=0
    )[0]
    edge_mask = (jnp.arange(max_edges) < n_edges).astype(pos.dtype)
    senders = (flat_idx // n).astype(jnp.int32)
    receivers = (flat_idx % n).astype(jnp.int32)
    shifts = shift[senders, receivers] * edge_mask[:, None]
    senders = jnp.where(edge_mask > 0, senders, pad_id)
    receivers = jnp.where(edge_mask > 0, receivers, pad_id)
    return senders, receivers, shifts, edge_mask, n_edges


def plan_cell_grid(
    cell, cutoff: float, n_atoms: int, capacity_factor: float = 2.5,
    pbc=None,
) -> tuple[tuple[int, int, int], int] | None:
    """HOST-side (trace-time) cell-list plan: grid dims + per-cell slot
    capacity, both static Python ints so the jitted build has fixed shapes.

    Grid dim along each axis = floor(perpendicular cell height / cutoff), so
    every cell is at least ``cutoff`` wide and a 27-cell neighborhood covers
    all pairs. A PERIODIC axis needs dim >= 3 — with fewer cells the +-1
    neighbor offsets alias under the wrap and pairs would double-count —
    and the plan returns None (caller falls back to the dense path, faster
    there anyway). An OPEN axis has no wrap, so slabs/wires bin fine with
    dim 1-2 (out-of-range offsets are masked, not wrapped). ``pbc`` None
    means fully periodic. Capacity = mean occupancy x ``capacity_factor``
    (+2): ``binned_radius_graph`` reports the true max occupancy so an
    overflow (strongly non-uniform density) is loud, never silent."""
    cell = np.asarray(cell, float).reshape(3, 3)
    pbc = np.ones(3, bool) if pbc is None else np.asarray(pbc, bool).reshape(3)
    vol = abs(np.linalg.det(cell))
    if vol <= 0:
        return None
    heights = np.array([
        vol / np.linalg.norm(np.cross(cell[(i + 1) % 3], cell[(i + 2) % 3]))
        for i in range(3)
    ])
    grid = np.floor(heights / float(cutoff)).astype(int)
    if (grid[pbc] < 3).any():
        return None
    grid = np.maximum(grid, 1)
    n_cells = int(grid.prod())
    cap = int(np.ceil(n_atoms / n_cells * capacity_factor)) + 2
    return (int(grid[0]), int(grid[1]), int(grid[2])), cap


# the 27 neighbor-cell offsets, a static constant folded into the trace
_CELL_OFFSETS = np.array(
    list(itertools.product((-1, 0, 1), repeat=3)), np.int32
)


def binned_radius_graph(
    pos: Array,
    cutoff: float,
    max_edges: int,
    cell: Array,
    pbc: Array,
    grid: tuple[int, int, int],
    capacity: int,
    pad_id: int = 0,
    fused: bool | None = None,
):
    """Jit-able cell-list radius graph with static shapes: O(N x 27 x
    capacity) memory instead of the dense O(N^2) matrix — ~10k-100k atoms
    in bounded memory (SURVEY S2.9's vesin role, on device).

    Same contract as ``dynamic_radius_graph``: returns ``(senders,
    receivers, shifts, edge_mask, n_edges)`` with min-image PBC displacement
    per candidate pair, so the two builders agree edge-for-edge wherever
    both apply. Overflow semantics: when a cell exceeds ``capacity`` (atoms
    dropped from the candidate set) the returned ``n_edges`` is poisoned to
    ``max_edges + max_occupancy`` — the caller's existing
    ``n_edges <= max_edges`` telltale trips instead of silently missing
    edges. ``grid``/``capacity`` come from ``plan_cell_grid`` (static).

    ``fused`` routes the build through the Pallas cell-list kernel
    (``ops.fused_cell_list``): the candidate walk + distance filter run in
    one windowed pass over cell-sorted atoms instead of materializing the
    ``[n, 27*capacity]`` candidate/displacement matrices below in HBM. Same
    edge SET, shifts, masks, and overflow poison; edge ORDER is cell-major
    instead of atom-major (consumers reduce over edges, so results differ
    only by fp association). Default (None): ``HYDRAGNN_FUSED_CELL_LIST``
    env flag, else on for TPU backends; statically ineligible geometries
    fall through to the XLA build either way."""
    from .ops import fused_cell_list

    if fused is None:
        fused = fused_cell_list._auto_enabled()
    if fused:
        out = fused_cell_list.fused_binned_radius_graph(
            pos, cutoff, max_edges, cell, pbc, grid, capacity, pad_id=pad_id
        )
        if out is not None:
            return out
    n = pos.shape[0]
    gx, gy, gz = (int(g) for g in grid)
    n_cells = gx * gy * gz
    if n * 27 * capacity >= 2**31:
        # jnp.nonzero flat indices are int32 (same guard as the dense build)
        raise ValueError(
            f"cell-list candidate matrix overflows int32 flat indices "
            f"(n={n} x 27 x capacity={capacity}); reduce capacity_factor or "
            "shard atoms over the mesh"
        )
    g = jnp.asarray([gx, gy, gz], jnp.int32)
    cellm = jnp.asarray(cell, pos.dtype).reshape(3, 3)
    inv = _inv3(cellm)
    pbc_b = jnp.asarray(pbc, bool).reshape(3)

    frac = _geom_dot(pos, inv)
    # wrapped (periodic) / clamped (open) coordinates are used for BINNING
    # only; distances below use the real positions
    fw = jnp.where(pbc_b, frac % 1.0, jnp.clip(frac, 0.0, 1.0 - 1e-9))
    idx3 = jnp.clip((fw * g).astype(jnp.int32), 0, g - 1)
    cid = (idx3[:, 0] * gy + idx3[:, 1]) * gz + idx3[:, 2]

    # bin via sort: rank of each atom within its cell = position - first
    # occurrence of its cell id in the sorted id array
    order = jnp.argsort(cid)
    cs = cid[order]
    rank = jnp.arange(n) - jnp.searchsorted(cs, cs, side="left")
    occ = jax.ops.segment_sum(jnp.ones(n, jnp.int32), cid, num_segments=n_cells)
    max_occ = occ.max()
    slots = jnp.full((n_cells, capacity), n, jnp.int32)  # n = empty sentinel
    slots = slots.at[cs, jnp.minimum(rank, capacity - 1)].set(
        order.astype(jnp.int32)
    )  # rank >= capacity overwrites the last slot; poisoned via max_occ below

    # candidate receivers: the 27 neighboring cells' slots
    offs = jnp.asarray(_CELL_OFFSETS)
    nbr3 = idx3[:, None, :] + offs[None, :, :]  # [n, 27, 3]
    wrapped = nbr3 % g
    valid = (pbc_b | ((nbr3 >= 0) & (nbr3 < g))).all(-1)  # [n, 27]
    ncid = (wrapped[..., 0] * gy + wrapped[..., 1]) * gz + wrapped[..., 2]
    cand = jnp.where(valid[..., None], slots[ncid], n)  # [n, 27, cap]
    c_tot = 27 * capacity
    cand = cand.reshape(n, c_tot)

    # min-image displacement, identical formula to the dense builder
    pos_pad = jnp.concatenate([pos, jnp.zeros((1, 3), pos.dtype)])
    disp = pos_pad[cand] - pos[:, None, :]  # [n, C, 3]
    wrap = jnp.round(_geom_dot(disp, inv)) * jnp.where(pbc_b, 1.0, 0.0)
    shift = -_geom_dot(wrap, cellm)
    disp = disp + shift
    d2 = jnp.sum(disp * disp, axis=-1)
    within = (
        (d2 <= cutoff * cutoff)
        & (cand != n)
        & (cand != jnp.arange(n, dtype=jnp.int32)[:, None])
    )
    n_edges = within.sum()
    flat_idx = jnp.nonzero(within.reshape(-1), size=max_edges, fill_value=0)[0]
    edge_mask = (jnp.arange(max_edges) < n_edges).astype(pos.dtype)
    senders = (flat_idx // c_tot).astype(jnp.int32)
    col = flat_idx % c_tot
    receivers = cand[senders, col]
    shifts = shift[senders, col] * edge_mask[:, None]
    senders = jnp.where(edge_mask > 0, senders, pad_id)
    receivers = jnp.where(edge_mask > 0, receivers.astype(jnp.int32), pad_id)
    n_edges = jnp.where(max_occ > capacity, max_edges + max_occ, n_edges)
    return senders, receivers, shifts, edge_mask, n_edges


class MDState(NamedTuple):
    pos: Array         # [N, 3]
    vel: Array         # [N, 3]
    forces: Array      # [N, 3]
    energy: Array      # scalar potential energy
    n_edges: Array     # neighbor count of the LAST rebuild
    max_n_edges: Array  # running max over the whole trajectory — the
    #                     overflow telltale (a transient spike between
    #                     recorded frames cannot hide)


def _make_potential_and_init(
    energy_fn, cutoff, max_edges, cell, pbc, pad_id, neighbor="auto",
    fused=None, capacity_factor=2.5,
):
    """Shared wiring for every integrator: the graph-rebuild potential and
    the initial-state constructor — one place for the neighbor/pad
    semantics, so NVE and NVT can never drift apart.

    ``neighbor``: "dense" = O(N^2) matrix build, "cell" = binned cell list
    (requires a periodic ``cell`` big enough for a 3x3x3 grid — raises
    otherwise), "auto" = cell list when plannable and N >= 512, else dense.
    ``fused``: Pallas cell-list kernel routing (``binned_radius_graph``).
    ``capacity_factor``: per-cell slot headroom for ``plan_cell_grid`` —
    raise it (MD.capacity_factor) after an ``n_edges`` overflow telltale."""

    if neighbor not in ("auto", "cell", "dense"):
        raise ValueError(
            f"neighbor={neighbor!r}: expected 'auto', 'cell', or 'dense'"
        )

    def potential(pos):
        spec = None
        if neighbor in ("auto", "cell") and cell is not None and pbc is not None:
            spec = plan_cell_grid(
                np.asarray(cell), cutoff, pos.shape[0],
                capacity_factor=capacity_factor, pbc=np.asarray(pbc),
            )
        if neighbor == "cell" and spec is None:
            raise ValueError(
                "neighbor='cell' needs a periodic cell with every "
                "perpendicular height >= 3*cutoff (plan_cell_grid returned "
                "None); use neighbor='dense' for small boxes"
            )
        if spec is not None and (neighbor == "cell" or pos.shape[0] >= 512):
            s, r, sh, em, ne = binned_radius_graph(
                pos, cutoff, max_edges, cell, pbc, spec[0], spec[1],
                pad_id=pad_id, fused=fused,
            )
        else:
            s, r, sh, em, ne = dynamic_radius_graph(
                pos, cutoff, max_edges, cell=cell, pbc=pbc, pad_id=pad_id
            )
        return energy_fn(pos, s, r, sh, em), ne

    def init(pos, vel) -> MDState:
        (e, ne), f = jax.value_and_grad(potential, has_aux=True)(pos)
        return MDState(pos=pos, vel=vel, forces=-f, energy=e, n_edges=ne,
                       max_n_edges=ne)

    return potential, init


def _wrap_positions(pos, cell, pbc):
    if cell is None or pbc is None:
        return pos
    c = jnp.asarray(cell, pos.dtype).reshape(3, 3)
    # subtract whole lattice vectors: exact, where re-multiplying the wrapped
    # fractional coordinates would round every position every step
    frac = _geom_dot(pos, _inv3(c))
    whole = jnp.where(jnp.asarray(pbc, bool).reshape(3), jnp.floor(frac), 0.0)
    return pos - _geom_dot(whole, c)


def make_md_step(
    energy_fn: Callable,
    masses: Array,
    dt: float,
    cutoff: float,
    max_edges: int,
    cell: Array | None = None,
    pbc: Array | None = None,
    pad_id: int = 0,
    neighbor: str = "auto",
    fused: bool | None = None,
    capacity_factor: float = 2.5,
):
    """Velocity-Verlet step with on-device graph rebuild.

    ``energy_fn(pos, senders, receivers, shifts, edge_mask) -> scalar``:
    wrap an MLIP model's energy head (or an analytic potential). Forces come
    from ``jax.grad`` of it — the same energy-conserving construction the
    MLIP training loss uses (``models/mlip.py``). ``pad_id``: where padded
    edge slots point (a model's reserved dummy-node index). ``neighbor``:
    see ``_make_potential_and_init`` — "auto" switches to the binned cell
    list at >= 512 atoms when the periodic cell allows it."""
    m = jnp.asarray(masses).reshape(-1, 1)
    potential, init = _make_potential_and_init(
        energy_fn, cutoff, max_edges, cell, pbc, pad_id, neighbor=neighbor,
        fused=fused, capacity_factor=capacity_factor,
    )

    @jax.jit
    def step(state: MDState) -> MDState:
        vel_half = state.vel + 0.5 * dt * state.forces / m
        pos = _wrap_positions(state.pos + dt * vel_half, cell, pbc)
        (e, ne), g = jax.value_and_grad(potential, has_aux=True)(pos)
        forces = -g
        vel = vel_half + 0.5 * dt * forces / m
        return MDState(pos=pos, vel=vel, forces=forces, energy=e, n_edges=ne,
                       max_n_edges=jnp.maximum(state.max_n_edges, ne))

    return init, step


def run_md(
    energy_fn: Callable,
    pos: Array,
    vel: Array,
    masses: Array,
    dt: float,
    n_steps: int,
    cutoff: float,
    max_edges: int,
    cell: Array | None = None,
    pbc: Array | None = None,
    record_every: int = 1,
    pad_id: int = 0,
    neighbor: str = "auto",
    fused: bool | None = None,
    capacity_factor: float = 2.5,
):
    """Roll a trajectory fully on device: ``lax.scan`` over MD steps, one
    compiled program. Returns (final_state, stacked recorded MDStates)."""
    if n_steps % record_every:
        raise ValueError(
            f"n_steps={n_steps} must be a multiple of record_every="
            f"{record_every} (the scan would silently drop the remainder)"
        )
    init, step = make_md_step(
        energy_fn, masses, dt, cutoff, max_edges, cell=cell, pbc=pbc,
        pad_id=pad_id, neighbor=neighbor, fused=fused,
        capacity_factor=capacity_factor,
    )
    state = init(jnp.asarray(pos), jnp.asarray(vel))
    n_rec = n_steps // record_every

    @jax.jit
    def segment(state):
        def body(s, _):
            def inner(s2, _):
                return step(s2), None

            s, _ = jax.lax.scan(inner, s, None, length=record_every)
            return s, s

        return jax.lax.scan(body, state, None, length=n_rec)

    return segment(state)


def make_langevin_step(
    energy_fn: Callable,
    masses: Array,
    dt: float,
    cutoff: float,
    max_edges: int,
    temperature: float,
    friction: float = 1.0,
    cell: Array | None = None,
    pbc: Array | None = None,
    pad_id: int = 0,
    neighbor: str = "auto",
    fused: bool | None = None,
    capacity_factor: float = 2.5,
):
    """NVT Langevin integrator (BAOAB splitting): the velocity-Verlet B/A
    halves wrap an Ornstein-Uhlenbeck velocity kick, which is exact for the
    friction/noise part — the standard low-dt-bias sampler. ``temperature``
    is in energy units (k_B T); the returned step takes and threads a PRNG
    key: ``state, key = step(state, key)``."""
    m = jnp.asarray(masses).reshape(-1, 1)
    c1 = jnp.exp(-friction * dt)
    c2 = jnp.sqrt(temperature * (1.0 - c1 * c1))
    potential, init = _make_potential_and_init(
        energy_fn, cutoff, max_edges, cell, pbc, pad_id, neighbor=neighbor,
        fused=fused, capacity_factor=capacity_factor,
    )

    @jax.jit
    def step(state: MDState, key):
        key, sub = jax.random.split(key)
        vel = state.vel + 0.5 * dt * state.forces / m          # B
        pos = state.pos + 0.5 * dt * vel                        # A
        noise = jax.random.normal(sub, vel.shape, vel.dtype)
        vel = c1 * vel + c2 * jnp.sqrt(1.0 / m) * noise         # O (exact OU)
        pos = _wrap_positions(pos + 0.5 * dt * vel, cell, pbc)  # A
        (e, ne), g = jax.value_and_grad(potential, has_aux=True)(pos)
        forces = -g
        vel = vel + 0.5 * dt * forces / m                       # B
        return (
            MDState(pos=pos, vel=vel, forces=forces, energy=e, n_edges=ne,
                    max_n_edges=jnp.maximum(state.max_n_edges, ne)),
            key,
        )

    return init, step


class NPTState(NamedTuple):
    pos: Array          # [N, 3]
    vel: Array          # [N, 3]
    forces: Array       # [N, 3]
    energy: Array       # scalar potential energy
    cell: Array         # [3, 3] — evolves under the barostat
    pressure: Array     # instantaneous pressure of the LAST step
    temperature: Array  # instantaneous kinetic temperature (energy units)
    n_edges: Array
    max_n_edges: Array


def make_berendsen_npt_step(
    energy_fn: Callable,
    masses: Array,
    dt: float,
    cutoff: float,
    max_edges: int,
    temperature: float,
    pressure: float,
    tau_t: float = 0.1,
    tau_p: float = 1.0,
    compressibility: float = 1.0,
    pbc: Array | None = None,
    pad_id: int = 0,
    max_scale_step: float = 0.02,
):
    """NPT via Berendsen weak coupling (beyond the reference, completing the
    NVE/NVT/NPT trio): a velocity-Verlet step, then velocity rescale toward
    ``temperature`` (k_B T, energy units) and isotropic position+cell
    rescale toward ``pressure``.

    The virial comes from ONE extra output of the same backward pass that
    computes forces: with the step's fixed neighbor list,
    ``U(eps) = energy_fn((1+eps) pos, (1+eps) shifts)`` and
    ``P = (2 KE - dU/deps) / (3 V)`` — the strain-derivative form of
    ``(2 KE + sum r.f) / (3V)``, exact for any differentiable potential
    (jax.grad w.r.t. the scalar strain), no pair-force bookkeeping.

    The cell is DYNAMIC state here, so the neighbor rebuild uses the dense
    min-image build (the binned cell list needs a trace-time static grid);
    per-step rescale factors are clipped to ``1 +- max_scale_step`` (the
    standard weak-coupling stability guard). Validity requires the cell to
    stay above 2x cutoff per perpendicular height, as for any min-image
    method."""
    import numpy as _np

    m = jnp.asarray(masses).reshape(-1, 1)
    pbc_arr = (jnp.ones(3, bool) if pbc is None
               else jnp.asarray(_np.asarray(pbc), bool).reshape(3))

    def energy_virial(pos, cell):
        """Rebuild + energy + forces + strain derivative, ONE backward
        pass — the single home of the virial formula for init and step."""
        s_, r_, sh, em, ne = dynamic_radius_graph(
            pos, cutoff, max_edges, cell=cell, pbc=pbc_arr, pad_id=pad_id
        )

        def u_of(pos_, eps):
            sc = 1.0 + eps
            return energy_fn(sc * pos_, s_, r_, sc * sh, em)

        e, (gpos, geps) = jax.value_and_grad(u_of, argnums=(0, 1))(pos, 0.0)
        return e, -gpos, geps, ne

    def t_and_p(vel, geps, cell):
        t_inst = temperature_of(vel, m)
        vol = jnp.abs(jnp.linalg.det(cell))
        p_inst = (2.0 * kinetic_energy(vel, m) - geps) / (3.0 * vol)
        return t_inst, p_inst

    def init(pos, vel, cell) -> NPTState:
        pos = jnp.asarray(pos)
        vel = jnp.asarray(vel)
        cell = jnp.asarray(cell, pos.dtype).reshape(3, 3)
        e, f, geps, ne = energy_virial(pos, cell)
        t_i, p_i = t_and_p(vel, geps, cell)
        return NPTState(pos=pos, vel=vel, forces=f, energy=e,
                        cell=cell, pressure=p_i, temperature=t_i,
                        n_edges=ne, max_n_edges=ne)

    @jax.jit
    def step(state: NPTState) -> NPTState:
        vel_half = state.vel + 0.5 * dt * state.forces / m
        pos = _wrap_positions(state.pos + dt * vel_half, state.cell, pbc_arr)
        e, forces, geps, ne = energy_virial(pos, state.cell)
        vel = vel_half + 0.5 * dt * forces / m
        t_inst, p_inst = t_and_p(vel, geps, state.cell)

        # weak couplings (clipped: the Berendsen stability guard)
        lam = jnp.sqrt(jnp.clip(
            1.0 + dt / tau_t * (temperature / jnp.maximum(t_inst, 1e-12) - 1.0),
            0.81, 1.21,
        ))
        # clip BEFORE the cube root: a large pressure excursion would make
        # the bracket negative, and (negative)**(1/3) is NaN — which a
        # post-hoc clip cannot catch (the whole state would go NaN forever)
        mu = jnp.clip(
            1.0 - compressibility * dt / tau_p * (pressure - p_inst),
            (1.0 - max_scale_step) ** 3, (1.0 + max_scale_step) ** 3,
        ) ** (1.0 / 3.0)
        return NPTState(
            pos=pos * mu, vel=vel * lam, forces=forces, energy=e,
            cell=state.cell * mu, pressure=p_inst, temperature=t_inst,
            n_edges=ne, max_n_edges=jnp.maximum(state.max_n_edges, ne),
        )

    return init, step


def temperature_of(vel: Array, masses: Array) -> Array:
    """Instantaneous kinetic temperature in energy units (k_B T):
    2 KE / (3 N)."""
    n = vel.shape[0]
    return 2.0 * kinetic_energy(vel, masses) / (3.0 * n)


def mlip_energy_fn(model, variables, template) -> Callable:
    """Adapt an MLIP model's energy head (``models.mlip``) to the
    ``dynamic_radius_graph`` edge arrays. ``template`` is a single-graph
    ``GraphBatch`` collated with the SAME max_edges padding — it supplies
    the static node features / masks; each call swaps in the current
    positions and neighbor arrays, so the whole MD step (graph rebuild +
    model forward + force grad + integration) stays one compiled program.

    The returned function takes the REAL atoms' positions (what
    ``make_md_step`` integrates) and scatters them into the template's
    padded coordinate array itself, so
    ``run_md(mlip_energy_fn(model, vars, template), ...)`` composes
    directly. Pass ``pad_id = template dummy-node index`` (``n_node - 1``)
    to the graph rebuild so pad edges follow the batch convention. Models
    whose forward reads per-edge attributes or angular triplets (DimeNet)
    are rejected: their edge_attr/idx_kj rows describe the TEMPLATE's
    topology and would silently go stale as the neighbor list evolves."""
    import numpy as _np

    from .models.mlip import make_graph_energy_fn

    spec = model.spec
    if spec.mpnn_type == "DimeNet":
        raise ValueError(
            "on-device MD cannot drive DimeNet: its angular triplet indices "
            "are enumerated on the host per topology (at collate time) and "
            "would go stale as the neighbor list evolves"
        )
    if template.edge_attr.shape[-1]:
        raise ValueError(
            "template carries per-edge attributes; they describe the "
            "template's topology, not the evolving neighbor list — use an "
            "edge_attr-free config for MD"
        )

    graph_energy = make_graph_energy_fn(model)
    n_real = int(_np.asarray(template.node_mask).sum())

    def energy(pos_real, senders, receivers, shifts, edge_mask):
        pos_full = template.pos.at[:n_real].set(pos_real)
        b = template.replace(
            senders=senders,
            receivers=receivers,
            edge_shifts=shifts,
            edge_mask=edge_mask,
            # the template's layout certificates were computed for ITS edge
            # order; the dynamic arrays are sender-major — a stale cert
            # would statically route the Pallas kernel onto an uncertified
            # layout (silently wrong sums), so drop to the dynamic check
            meta=None,
        )
        return graph_energy(variables, pos_full, b).sum()

    return energy


def kinetic_energy(vel: Array, masses: Array) -> Array:
    m = jnp.asarray(masses).reshape(-1, 1)
    return 0.5 * jnp.sum(m * vel * vel)


__all__ = [
    "MDConfig", "MDState", "NPTState", "binned_radius_graph",
    "dynamic_radius_graph", "kinetic_energy", "make_berendsen_npt_step",
    "make_langevin_step", "make_md_step", "md_config_defaults",
    "mlip_energy_fn", "plan_cell_grid", "run_md", "temperature_of",
]
