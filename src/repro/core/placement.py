"""Structured placement — backend kind + device + replica, end to end.

Courier-FPGA's core move is putting every pipeline stage on the execution
resource it fits best: predefined hardware modules on the FPGA fabric,
software filters on CPU cores.  The seed reproduction encoded that choice
as a bare ``"hw"/"sw"`` string on each IR node, which was enough to pick an
implementation but said nothing about *where* the chosen implementation
runs — and PR 4's stage replication could therefore only widen a stage
across host threads.  This module replaces the string with a structured
:class:`Placement` (backend kind + device ordinal / mesh coordinate +
replica index) and adds the :class:`DeviceInventory` the planner consumes
to map stage replicas onto *real* devices (N replicas of a stage pinned to
N chips/cores), the way portable accelerator pipelines describe placement
as a first-class object rather than a two-valued tag.

THIS MODULE IS THE ONLY PLACE the literal kind strings may appear — the
back-compat parser (:meth:`Placement.parse`) accepts the legacy strings and
everything else goes through the :data:`HW`/:data:`SW` constants and the
:func:`is_hw`/:func:`is_sw`/:func:`placement_kind` helpers.  A grep-guard
test (AST-based, so docstrings are exempt but code is not) enforces it.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field, replace
from typing import Any, Iterator, Sequence

# --------------------------------------------------------------------------- #
# Backend kinds — the ONLY allowed spelling of the legacy strings
# --------------------------------------------------------------------------- #
HW = "hw"                    # accelerated module (Pallas kernel / FPGA module)
SW = "sw"                    # software fallback (plain XLA / CPU function)
UNASSIGNED = "unassigned"    # backend not yet chosen (pre-database lookup)

_KINDS = (HW, SW, UNASSIGNED)

# Reserved-core headroom knob for the budget governor (cores the widening
# pass must leave free for the OS / serving threads / the admission loop).
RESERVED_CORES_ENV = "REPRO_RESERVED_CORES"
DEFAULT_RESERVED_CORES = 1


# --------------------------------------------------------------------------- #
# Placement
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class Placement:
    """Where one IR node (or one stage replica) executes.

    ``kind``
        Backend kind: :data:`HW` (accelerated module), :data:`SW`
        (software fallback), or :data:`UNASSIGNED`.
    ``device``
        Device ordinal into the active :class:`DeviceInventory`
        (``None`` = unpinned: the process-default device).
    ``mesh_coord``
        Optional mesh coordinate of the device (``launch/mesh.py`` /
        TPU ``coords``) for pod-topology-aware callers.
    ``replica``
        Replica index when the owning stage is widened (0 for serial
        stages) — which of the N parallel workers this placement names.
    """

    kind: str = UNASSIGNED
    device: int | None = None
    mesh_coord: tuple[int, ...] | None = None
    replica: int = 0

    def __post_init__(self) -> None:
        if self.kind not in _KINDS:
            raise ValueError(f"unknown placement kind {self.kind!r}; "
                             f"expected one of {_KINDS}")
        if self.mesh_coord is not None:
            object.__setattr__(self, "mesh_coord",
                               tuple(int(c) for c in self.mesh_coord))

    # -- predicates --------------------------------------------------------- #
    @property
    def is_hw(self) -> bool:
        return self.kind == HW

    @property
    def is_sw(self) -> bool:
        return self.kind == SW

    @property
    def is_assigned(self) -> bool:
        return self.kind != UNASSIGNED

    # -- constructors ------------------------------------------------------- #
    @classmethod
    def hw(cls, device: int | None = None, replica: int = 0,
           mesh_coord: tuple[int, ...] | None = None) -> "Placement":
        return cls(kind=HW, device=device, replica=replica,
                   mesh_coord=mesh_coord)

    @classmethod
    def sw(cls, device: int | None = None, replica: int = 0,
           mesh_coord: tuple[int, ...] | None = None) -> "Placement":
        return cls(kind=SW, device=device, replica=replica,
                   mesh_coord=mesh_coord)

    @classmethod
    def unassigned(cls) -> "Placement":
        return cls()

    @classmethod
    def parse(cls, value: Any) -> "Placement":
        """THE back-compat parser: legacy strings / dicts → Placement.

        Accepts a :class:`Placement` (returned as-is), the legacy
        ``"hw"``/``"sw"``/``"unassigned"`` strings (seed IR, user
        ``edit_ir`` hooks that pin placements by string), a dict (JSON
        deserialization of a structured placement), or ``None``
        (unassigned).  Every other layer calls this instead of comparing
        raw strings.
        """
        if isinstance(value, cls):
            return value
        if value is None:
            return cls()
        if isinstance(value, str):
            return cls(kind=value)          # __post_init__ validates
        if isinstance(value, dict):
            d = dict(value)
            if d.get("mesh_coord") is not None:
                d["mesh_coord"] = tuple(d["mesh_coord"])
            return cls(**d)
        raise TypeError(f"cannot parse a Placement from {type(value).__name__}")

    # -- derivation --------------------------------------------------------- #
    def with_kind(self, kind: str) -> "Placement":
        """Same device/replica pinning, new backend kind (assign_placements
        must not wipe a device assignment when it re-resolves the kind)."""
        return replace(self, kind=kind)

    def on(self, device: int | None, replica: int = 0,
           mesh_coord: tuple[int, ...] | None = None) -> "Placement":
        """Same kind, pinned to ``device`` as replica ``replica``."""
        return replace(self, device=device, replica=replica,
                       mesh_coord=mesh_coord)

    @property
    def key(self) -> tuple:
        """Hashable identity used in StageFn / executor cache keys."""
        return (self.kind, self.device, self.replica)

    # -- rendering ---------------------------------------------------------- #
    def short(self) -> str:
        """Compact label for the IR pretty-printer: ``hw``, ``hw@2``,
        ``hw@2.1`` (device 2, replica 1)."""
        s = self.kind
        if self.device is not None:
            s += f"@{self.device}"
            if self.replica:
                s += f".{self.replica}"
        return s

    def __str__(self) -> str:               # pragma: no cover - trivial
        return self.short()

    def __repr__(self) -> str:
        return f"Placement({self.short()!r})"


# -- helpers that tolerate legacy values ------------------------------------ #
def placement_kind(value: Any) -> str:
    """Backend kind of a placement-like value (string or Placement)."""
    return Placement.parse(value).kind


def is_hw(value: Any) -> bool:
    """True when a placement-like value names the accelerated backend.

    ``None`` (and anything unassigned) is not hw — callers use this as the
    single predicate instead of ``== "hw"`` string comparisons.
    """
    return value is not None and Placement.parse(value).is_hw


def is_sw(value: Any) -> bool:
    return value is not None and Placement.parse(value).is_sw


# --------------------------------------------------------------------------- #
# Device inventory — what the planner places replicas onto
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class InventoryDiff:
    """Structured result of :meth:`DeviceInventory.refresh`.

    ``old``/``new`` are the inventories before/after the probe; ``lost``
    and ``gained`` name ordinals in the respective inventory's numbering;
    ``survivors`` maps each surviving OLD ordinal to its NEW ordinal (the
    re-densified numbering after a loss), which is how profiler stats
    keyed by old ordinals follow their device across a re-plan.
    """

    old: "DeviceInventory"
    new: "DeviceInventory"
    lost: tuple[int, ...] = ()         # old ordinals no longer present
    gained: tuple[int, ...] = ()       # new ordinals with no old identity
    survivors: dict = field(default_factory=dict)   # old ordinal -> new

    @property
    def changed(self) -> bool:
        return bool(self.lost or self.gained)

    def describe(self) -> str:
        return (f"InventoryDiff({len(self.old)} -> {len(self.new)} devices; "
                f"lost {list(self.lost)}, gained {list(self.gained)})")


@dataclass(frozen=True)
class DeviceSpec:
    """One placeable device: ordinal + platform + optional topology."""

    ordinal: int                       # index into the inventory
    platform: str = "cpu"              # "tpu" | "cpu"
    device_id: int | None = None       # backend device id (jax.Device.id)
    coord: tuple[int, ...] | None = None   # mesh/pod coordinate when known
    speed: float = 1.0                 # relative throughput vs class baseline
    kind: str = "cpu"                  # jax.Device.device_kind (peaks key)

    def __post_init__(self) -> None:
        # the kind picks the roofline peaks: a TPU spec left with the CPU
        # default kind would be costed as a host core
        if (self.platform == "cpu") != (self.kind == "cpu"):
            raise ValueError(f"device kind {self.kind!r} does not match "
                             f"platform {self.platform!r}")
        if self.coord is not None:
            object.__setattr__(self, "coord",
                               tuple(int(c) for c in self.coord))
        if self.speed <= 0.0:
            raise ValueError(f"device speed must be > 0 (got {self.speed})")


class DeviceInventory:
    """The placeable devices the planner maps stage replicas onto.

    Built from ``jax.devices()`` (:meth:`detect`), a production mesh
    (:meth:`from_mesh`), or synthetically (:meth:`host`, for planner unit
    tests that need a 4-device inventory without forcing host devices).
    The inventory is what :func:`repro.core.partition.assign_replicas`
    consumes instead of an abstract worker budget: replica ``w`` of a
    widened stage is pinned to a concrete ordinal here, and the executor
    ``jax.device_put``\\ s that replica's groups onto the mapped
    ``jax.Device``.
    """

    def __init__(self, specs: Sequence[DeviceSpec],
                 jax_devices: Sequence[Any] | None = None):
        if not specs:
            raise ValueError("a DeviceInventory needs at least one device")
        self.specs: tuple[DeviceSpec, ...] = tuple(specs)
        for i, s in enumerate(self.specs):
            if s.ordinal != i:
                raise ValueError(f"spec #{i} carries ordinal {s.ordinal}; "
                                 "ordinals must be dense and ordered")
        if jax_devices is not None and len(jax_devices) != len(self.specs):
            raise ValueError(f"{len(jax_devices)} jax devices for "
                             f"{len(self.specs)} specs")
        self._jax = tuple(jax_devices) if jax_devices is not None else None

    # -- constructors ------------------------------------------------------- #
    @classmethod
    def detect(cls, limit: int | None = None) -> "DeviceInventory":
        """Inventory over ``jax.devices()`` (optionally the first ``limit``)."""
        import jax

        devs = list(jax.devices())
        if limit is not None:
            if limit < 1:
                raise ValueError(f"limit must be >= 1 (got {limit})")
            devs = devs[:limit]
        specs = [DeviceSpec(ordinal=i, platform=str(d.platform),
                            device_id=int(getattr(d, "id", i)),
                            coord=tuple(getattr(d, "coords", None) or ())
                            or None, kind=str(d.device_kind))
                 for i, d in enumerate(devs)]
        return cls(specs, jax_devices=devs)

    @classmethod
    def from_mesh(cls, mesh: Any) -> "DeviceInventory":
        """Inventory over a mesh's devices, coords = mesh coordinates."""
        import numpy as np

        arr = np.asarray(mesh.devices)
        specs, devs = [], []
        for i, idx in enumerate(np.ndindex(arr.shape)):
            d = arr[idx]
            specs.append(DeviceSpec(ordinal=i, platform=str(d.platform),
                                    device_id=int(getattr(d, "id", i)),
                                    coord=tuple(int(c) for c in idx),
                                    kind=str(d.device_kind)))
            devs.append(d)
        return cls(specs, jax_devices=devs)

    @classmethod
    def host(cls, n: int, kind: str = "cpu") -> "DeviceInventory":
        """Synthetic n-device inventory of ``kind`` devices (a
        ``jax.Device.device_kind``: ``"cpu"`` or a TPU's, such as
        ``"TPU v5 lite"``) for planner tests and dry planning.

        Carries no ``jax.Device`` objects, so executors treat every
        ordinal as the default device (planning-only inventory).  Each
        spec gets a synthetic stable ``device_id`` so :meth:`refresh` can
        match survivors across a :meth:`drop` re-densification.
        """
        if kind != "cpu" and not kind.startswith("TPU"):
            raise ValueError(f"no platform known for device kind {kind!r}")
        platform = "cpu" if kind == "cpu" else "tpu"
        return cls([DeviceSpec(ordinal=i, platform=platform, device_id=i,
                               kind=kind) for i in range(n)])

    # -- queries ------------------------------------------------------------ #
    def __len__(self) -> int:
        return len(self.specs)

    def __iter__(self) -> Iterator[DeviceSpec]:
        return iter(self.specs)

    def _check(self, ordinal: int) -> int:
        # explicit range check: Python's negative indexing would silently
        # alias ordinal -1 to the last device while stats/profiles report
        # the bogus ordinal, so reject anything outside [0, len)
        if not 0 <= ordinal < len(self.specs):
            raise IndexError(f"device ordinal {ordinal} out of range for a "
                             f"{len(self.specs)}-device inventory")
        return ordinal

    def spec(self, ordinal: int) -> DeviceSpec:
        return self.specs[self._check(ordinal)]

    def jax_device(self, ordinal: int) -> Any | None:
        """The mapped ``jax.Device`` (None for planning-only inventories)."""
        self._check(ordinal)
        if self._jax is None:
            return None
        return self._jax[ordinal]

    def device_class(self, ordinal: int):
        """Roofline constants for the device's ``device_kind``."""
        from .costmodel import device_class
        return device_class(self.spec(ordinal).kind)

    @property
    def homogeneous(self) -> bool:
        return len({(s.platform, s.speed) for s in self.specs}) <= 1

    def worker_budget(self, n_stages: int = 1,
                      reserved_cores: int | None = None) -> int:
        """Budget governor over this inventory (see
        :func:`default_worker_budget`): never below one worker per stage
        or one worker per device — a 4-chip inventory must be widenable
        to 4 replicas even on a small host, because the workers there
        only *drive* devices (they block in ``device_put`` / execute,
        they don't compute).
        """
        return max(default_worker_budget(n_stages, reserved_cores),
                   len(self.specs))

    def describe(self) -> str:
        rows = [f"DeviceInventory({len(self.specs)} devices)"]
        for s in self.specs:
            c = f" coord={s.coord}" if s.coord else ""
            rows.append(f"  #{s.ordinal} {s.platform}"
                        f"(id={s.device_id}){c} x{s.speed:g}")
        return "\n".join(rows)

    # -- elastic inventory --------------------------------------------------- #
    def _identity(self, ordinal: int) -> tuple:
        # device identity across probes: the backend id when one exists
        # (real inventories), the ordinal itself for planning-only
        # inventories (host(n) has no ids — position IS identity there)
        s = self.specs[ordinal]
        return (s.platform, s.device_id if s.device_id is not None
                else ("ordinal", ordinal))

    def refresh(self, probe: Any = None) -> InventoryDiff:
        """Re-detect the device set and diff it against this inventory.

        ``probe`` is a zero-arg callable returning the NEW
        :class:`DeviceInventory` (default: :meth:`detect` — the real
        re-probe; tests and fault benchmarks pass
        ``FaultInjector.surviving``).  Devices are matched by identity
        ``(platform, device_id)``, so a loss that re-densifies the
        ordinals still maps every survivor old→new in the returned
        :class:`InventoryDiff`.
        """
        new = probe() if probe is not None else DeviceInventory.detect()
        old_ids = {self._identity(i): i for i in range(len(self.specs))}
        new_ids = {new._identity(j): j for j in range(len(new.specs))}
        survivors = {old_ids[k]: new_ids[k] for k in old_ids if k in new_ids}
        lost = tuple(sorted(i for k, i in old_ids.items() if k not in new_ids))
        gained = tuple(sorted(j for k, j in new_ids.items()
                              if k not in old_ids))
        return InventoryDiff(old=self, new=new, lost=lost, gained=gained,
                             survivors=survivors)

    def drop(self, ordinals: Any) -> "DeviceInventory":
        """Survivors-only inventory: this one minus ``ordinals``,
        re-densified (survivor k becomes ordinal ``rank(k)``) with
        platform/id/coord/speed and any mapped ``jax.Device`` preserved.
        """
        gone = {self._check(int(o)) for o in ordinals}
        keep = [i for i in range(len(self.specs)) if i not in gone]
        if not keep:
            raise ValueError("cannot drop every device in the inventory")
        specs = [replace(self.specs[i], ordinal=j)
                 for j, i in enumerate(keep)]
        devs = [self._jax[i] for i in keep] if self._jax is not None else None
        return DeviceInventory(specs, jax_devices=devs)

    def reweighted(self, factors: dict) -> "DeviceInventory":
        """Copy with per-ordinal speed multipliers applied (clamped
        positive) — how the replanner de-weights an unhealthy device so
        ``assign_replicas`` widens onto its healthy peers instead."""
        specs = [replace(s, speed=max(s.speed
                                      * float(factors.get(s.ordinal, 1.0)),
                                      1e-6))
                 for s in self.specs]
        return DeviceInventory(specs, jax_devices=self._jax)


# --------------------------------------------------------------------------- #
# Budget governor — widen only when spare cores exist
# --------------------------------------------------------------------------- #
def default_worker_budget(n_stages: int = 1,
                          reserved_cores: int | None = None) -> int:
    """Host-derived default worker budget for the widening pass.

    ``os.cpu_count()`` minus a reserved-core headroom knob
    (``REPRO_RESERVED_CORES`` env var, default 1 — cores kept free for the
    OS, the admission loop, and serving threads), floored at one worker
    per stage (the hard minimum :func:`~repro.core.partition.
    assign_replicas` enforces).  On a saturated host this collapses to the
    floor, so the planner widens nothing — exactly the governor the
    ROADMAP asks for.  An explicit ``worker_budget=`` everywhere remains
    the override.
    """
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1 (got {n_stages})")
    if reserved_cores is None:
        reserved_cores = int(os.environ.get(RESERVED_CORES_ENV,
                                            DEFAULT_RESERVED_CORES))
    if reserved_cores < 0:
        raise ValueError(f"reserved_cores must be >= 0 (got {reserved_cores})")
    cores = os.cpu_count() or 1
    return max(n_stages, cores - reserved_cores)


AUTO_BUDGET = "auto"      # sentinel: derive the budget from the governor


def resolve_worker_budget(worker_budget: Any, n_stages: int,
                          inventory: "DeviceInventory | None" = None,
                          ) -> int | None:
    """Normalize a worker-budget argument.

    * an int — the explicit override, returned as-is;
    * :data:`AUTO_BUDGET` — the governor (inventory-aware when one is
      given);
    * ``None`` — the governor when an inventory is present (a caller who
      handed the planner real devices wants them used), else ``None``
      (no widening, the legacy meaning).
    """
    if worker_budget is None:
        if inventory is None:
            return None
        return inventory.worker_budget(n_stages)
    if worker_budget == AUTO_BUDGET:
        if inventory is not None:
            return inventory.worker_budget(n_stages)
        return default_worker_budget(n_stages)
    return int(worker_budget)
