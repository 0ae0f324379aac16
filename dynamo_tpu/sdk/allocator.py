"""TPU resource allocator — per-replica chip assignment for local serving.

The reference partitions host GPUs across service worker replicas and
exports ``CUDA_VISIBLE_DEVICES`` per process (reference:
deploy/sdk/src/dynamo/sdk/cli/allocator.py:53-151 — ``assign_gpus`` +
``get_resource_envs``).  Without this, two ``workers=2`` services on one
host would all claim the whole TPU slice and the second process would hang
in libtpu chip init.  The TPU-native analog partitions the host's chips and
exports ``TPU_VISIBLE_CHIPS`` per replica process.

TPU-first deviations from the reference:

- **No fractional chips.** The reference fractionally time-shares a GPU
  between services (``assign_gpus`` count<1).  libtpu claims a chip
  exclusively for one process — a fractional request is a deployment error
  here, not a scheduling strategy, so it raises :class:`ResourceError`.
- **Contiguous runs.** Chips are assigned as contiguous index runs so a
  tp>1 replica's chips sit on adjacent ICI links (chip index order follows
  the physical torus on single-host slices); the reference assigns
  arbitrary free GPU indices.
- **Fail fast on over-subscription.** The reference logs a warning and
  serves anyway (CUDA time-shares); on TPU the over-subscribed process
  would deadlock on the chip claim, so exhausting the inventory raises
  unless ``DYN_DISABLE_AUTO_TPU_ALLOCATION=1`` opts the deployment out of
  allocation entirely (the operator/K8s path does its own placement via
  the ``google.com/tpu`` extended resource — deploy/operator.py).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from dynamo_tpu.utils.logging import get_logger
from dynamo_tpu.utils import knobs

logger = get_logger("sdk.allocator")

# opt-out switch, mirroring the reference's DYN_DISABLE_AUTO_GPU_ALLOCATION
DISABLE_ENV = "DYN_DISABLE_AUTO_TPU_ALLOCATION"
# the env var libtpu reads to restrict a process to a chip subset; also
# what ChipInventory.detect() honors when the parent was itself restricted
VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"


class ResourceError(RuntimeError):
    """Chip request that cannot be satisfied (or is meaningless on TPU)."""


@dataclass(frozen=True)
class ChipInventory:
    """The TPU chips this host may hand out, as libtpu chip indices."""

    chips: tuple[int, ...]
    device_kind: str = "tpu"

    @classmethod
    def detect(cls, env: dict | None = None) -> "ChipInventory":
        """Inventory from the environment, cheapest signal first.

        1. ``TPU_VISIBLE_CHIPS`` — already restricted (nested supervisors,
           operator-managed pods): inherit exactly that subset.
        2. ``DYN_TPU_CHIP_COUNT`` — explicit operator knob.
        3. An initialized jax TPU backend, if one already exists in this
           process (never initializes jax here: supervisor CLIs must not
           pay — or wedge on — device bring-up just to plan processes).
        4. Otherwise: empty inventory (CPU host / no TPU visible).
        """
        env = os.environ if env is None else env
        visible = env.get(VISIBLE_CHIPS_ENV)
        if visible:
            return cls(chips=tuple(int(c) for c in visible.split(",") if c != ""))
        count = knobs.get("DYN_TPU_CHIP_COUNT", env=env)
        if count:
            return cls(chips=tuple(range(count)))
        try:
            import jax
            from jax._src import xla_bridge

            # private check on purpose: the PUBLIC backends() call would
            # INITIALIZE the backend, i.e. claim the TPU from the planner
            # process — the one thing detect() must never do
            if xla_bridge._backends and jax.default_backend() == "tpu":
                return cls(
                    chips=tuple(d.id for d in jax.local_devices()),
                    device_kind=jax.local_devices()[0].device_kind,
                )
        except Exception:  # noqa: BLE001 — detection must never raise
            pass
        return cls(chips=())


@dataclass
class ResourceAllocator:
    """Hands out disjoint chip sets to service replicas on one host."""

    inventory: ChipInventory
    _free: list[int] = field(init=False)
    assignments: dict[str, list[list[int]]] = field(default_factory=dict)

    def __post_init__(self) -> None:
        self._free = sorted(self.inventory.chips)

    @property
    def remaining(self) -> int:
        return len(self._free)

    def assign_chips(self, count: float, service_name: str = "") -> list[int]:
        """Claim ``count`` chips as a contiguous run; they leave the pool.

        Raises :class:`ResourceError` on fractional requests (TPU chips are
        process-exclusive) and on over-subscription (the claim would
        deadlock at runtime, so fail at plan time)."""
        if count != int(count) or count < 1:
            raise ResourceError(
                f"{service_name or 'service'}: requested {count} TPU chips — "
                "chips are process-exclusive (libtpu claims whole chips); "
                "use integer counts, or omit the tpu resource for CPU-only "
                "services"
            )
        count = int(count)
        if count > len(self._free):
            raise ResourceError(
                f"{service_name or 'service'}: requested {count} TPU chips "
                f"but only {len(self._free)} of {len(self.inventory.chips)} "
                f"remain unassigned; set {DISABLE_ENV}=1 to manage "
                f"{VISIBLE_CHIPS_ENV} manually"
            )
        # prefer a contiguous run (ICI adjacency); fall back to the lowest
        # free indices when fragmentation leaves no run long enough
        run = self._contiguous_run(count)
        assigned = run if run is not None else self._free[:count]
        for c in assigned:
            self._free.remove(c)
        if service_name:
            self.assignments.setdefault(service_name, []).append(list(assigned))
        logger.info(
            "assigned chips %s to %s (%d remain)",
            assigned, service_name or "<anon>", len(self._free),
        )
        return list(assigned)

    def _contiguous_run(self, count: int) -> list[int] | None:
        free = self._free
        for i in range(len(free) - count + 1):
            window = free[i : i + count]
            if window[-1] - window[0] == count - 1:
                return list(window)
        return None

    def replica_envs(
        self, *, tpu: float, workers: int, service_name: str = ""
    ) -> list[dict[str, str]]:
        """One env overlay per worker replica, each with a disjoint chip set
        (the reference's local-deployment branch: one ``assign_gpus`` call
        per worker → per-worker ``CUDA_VISIBLE_DEVICES``)."""
        envs = []
        for _ in range(workers):
            chips = self.assign_chips(tpu, service_name)
            envs.append({
                VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chips),
                # the framework's own record, independent of libtpu's var
                "DYN_TPU_CHIPS": ",".join(str(c) for c in chips),
            })
        return envs


def plan_resource_envs(
    services: list, *, inventory: ChipInventory | None = None,
    env: dict | None = None,
) -> dict[str, list[dict[str, str]]]:
    """Per-service, per-replica env overlays for a whole dependency closure.

    ``services`` is a list of @service-decorated classes (sdk/graph.py).
    Services without a ``tpu`` resource get empty overlays.  Returns {} for
    every service when allocation is disabled, or on a CPU-only host
    (``JAX_PLATFORMS=cpu``: nothing to claim) — processes then see whatever
    the parent saw.  A host that may have chips but gives no chip count
    (``TPU_VISIBLE_CHIPS`` / ``DYN_TPU_CHIP_COUNT``) raises: unassigned
    replicas would each claim every chip and the second would hang."""
    env = os.environ if env is None else env
    if knobs.get(DISABLE_ENV, env=env):
        return {}
    inventory = ChipInventory.detect(env) if inventory is None else inventory
    requested = {
        cls._dyn_service.name: cls._dyn_service
        for cls in services
        if (cls._dyn_service.resources or {}).get("tpu")
    }
    if not requested:
        return {}
    if not inventory.chips:
        _require_cpu_host(env, f"services {sorted(requested)}")
        return {}
    allocator = ResourceAllocator(inventory)
    return {
        name: allocator.replica_envs(
            tpu=config.resources["tpu"], workers=config.workers,
            service_name=name,
        )
        for name, config in requested.items()
    }


def _require_cpu_host(env, who: str) -> None:
    """No chip count is known: fine on a CPU-only host, an error anywhere
    a chip might be (the planner must not guess, and must not touch jax)."""
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        logger.info("%s: CPU-only host (JAX_PLATFORMS=cpu), no chips to assign", who)
        return
    raise ResourceError(
        f"{who} need TPU chips but this host's chip count is unknown: set "
        f"DYN_TPU_CHIP_COUNT (or {VISIBLE_CHIPS_ENV}) so each process gets "
        "its own chips — unassigned, every process claims every chip and "
        f"the second one hangs.  (JAX_PLATFORMS=cpu runs the graph on the "
        f"CPU; {DISABLE_ENV}=1 leaves placement to you.)"
    )


def one_chip_each(workers: int, who: str, env: dict | None = None) -> list[dict[str, str]]:
    """Env overlays giving each of ``workers`` single-chip processes its own
    chip, for launchers that build their ProcessSpecs by hand."""
    env = os.environ if env is None else env
    if knobs.get(DISABLE_ENV, env=env):
        return [{} for _ in range(workers)]
    inventory = ChipInventory.detect(env)
    if not inventory.chips:
        _require_cpu_host(env, who)
        return [{} for _ in range(workers)]
    return ResourceAllocator(inventory).replica_envs(
        tpu=1, workers=workers, service_name=who
    )
