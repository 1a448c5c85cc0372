"""Production mesh construction and elastic-failover mesh surgery.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required for the
dry-run's placeholder-device trick and for keeping smoke tests on 1 device.

The failover pieces live here too: :class:`MeshLostError` (the
infrastructure fault a hung or raising collective surfaces as),
:func:`degraded_context` (rebuild the ``(1, n)`` host mesh over the
surviving devices so every StepProgram admissibility decision re-runs
against the shrunken model axis) and :class:`SimulatedDeviceLoss`
(the ``--inject dev-loss`` fault surface for the fake multi-device mesh).
"""

from __future__ import annotations

import time

import jax

from repro.distributed.context import MeshContext


class MeshLostError(RuntimeError):
    """A device (or a whole host) left the mesh: a collective raised or
    hung past the step deadline.  Carries the surviving device list when
    the detector knows it (the simulator always does; a real runtime
    error leaves it ``None`` and the failover falls back to a configured
    survivor count).  Distinct from the numerical fault ladder — the
    *logical* state is not suspect, only the topology is, so the sentinel
    escalates straight to failover instead of climbing strikes.
    """

    def __init__(self, message: str, survivors: list | None = None,
                 step: int | None = None):
        super().__init__(message)
        self.survivors = list(survivors) if survivors is not None else None
        self.step = step


# XLA status codes a lost, hung or restarted participant surfaces as.  Any
# other runtime error (RESOURCE_EXHAUSTED, INVALID_ARGUMENT, INTERNAL, ...)
# is a fault of the program or its sizing, which a rebuilt mesh would only
# repeat, so it propagates instead of entering failover.
DEVICE_LOSS_CODES = ("UNAVAILABLE", "ABORTED", "DEADLINE_EXCEEDED",
                     "DATA_LOSS")


def is_device_loss(err: BaseException) -> bool:
    """Whether a ``JaxRuntimeError`` reports a lost device rather than a
    fault of the program (judged by its leading XLA status code)."""
    return str(err).lstrip().startswith(DEVICE_LOSS_CODES)


class SimulatedDeviceLoss:
    """Host-side stand-in for a lost mesh participant (``--inject
    dev-loss@N``).  On the fake ``--xla_force_host_platform_device_count``
    mesh the XLA collectives cannot actually be made to fail, so the
    simulator guards the two host/device interaction points the real
    failure would poison: ``raise`` mode fails at dispatch (XLA surfaces
    a dead participant as a runtime error on the calling thread), and
    ``hang`` mode blocks the metric drain (a collective that never
    completes) — which the step-deadline watchdog must convert into
    :class:`MeshLostError` on its own.

    Unlike the numerical injections (consumed at their step), an armed
    device loss STAYS armed — a lost device stays lost — until the
    failover rebuilds the mesh from the survivors and calls
    :meth:`disarm`.
    """

    def __init__(self):
        self.fail_step: int | None = None
        self.survivors: list = []
        self.mode = "raise"
        self.hang_s = 30.0

    @property
    def armed(self) -> bool:
        return self.fail_step is not None

    def arm(self, step: int, survivors, mode: str = "raise",
            hang_s: float = 30.0) -> None:
        self.fail_step = step
        self.survivors = list(survivors)
        self.mode = mode
        self.hang_s = hang_s

    def disarm(self) -> None:
        self.fail_step = None

    def check(self, step: int, where: str) -> None:
        """Called at dispatch and drain; raises/hangs past the fault step."""
        if self.fail_step is None or step < self.fail_step:
            return
        if self.mode == "hang":
            if where != "drain":
                return          # a hung collective only shows at the sync
            time.sleep(self.hang_s)
            raise MeshLostError(
                f"simulated hung collective at step {step} (device loss)",
                survivors=self.survivors, step=step)
        raise MeshLostError(
            f"simulated failed collective at step {step}: device subset "
            f"left the mesh ({len(self.survivors)} survivors)",
            survivors=self.survivors, step=step)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips) mesh.

    Axes: ``data`` carries batch + FSDP; ``model`` carries TP/EP; ``pod``
    (multi-pod only) is pure DP across pods — ICI-dense collectives stay
    within a pod, only the gradient all-reduce crosses DCN.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_context(*, multi_pod: bool = False) -> MeshContext:
    mesh = make_production_mesh(multi_pod=multi_pod)
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    return MeshContext(mesh=mesh, batch_axes=batch_axes, model_axis="model")


def smoke_context() -> MeshContext:
    """Single-device (1, 1) mesh for CPU smoke tests and benches."""
    import numpy as np
    from jax.sharding import Mesh

    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return MeshContext(mesh=Mesh(dev, ("data", "model")),
                       batch_axes=("data",))


def host_context(limit: int | None = None) -> MeshContext:
    """(1, N) mesh over ALL local devices — exercises real model-axis
    collectives on a fake multi-device host (XLA_FLAGS
    ``--xla_force_host_platform_device_count=8``).  Used by the
    fault-injection acceptance runs so every sharding regime's
    quarantine path executes with genuine psums.  ``limit`` caps N (the
    first ``limit`` devices) — how the failover tests build the
    uninjected degraded-mesh reference runs."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if limit:
        devs = devs[:limit]
    dev = np.array(devs).reshape(1, len(devs))
    return MeshContext(mesh=Mesh(dev, ("data", "model")),
                       batch_axes=("data",))


def degraded_context(survivors) -> MeshContext:
    """Rebuild the ``(1, n)`` host-style mesh over the surviving devices
    after a :class:`MeshLostError`.

    The layout mirrors :func:`host_context` (``data`` x ``model`` axes,
    all survivors on the model axis) so the downstream re-planning —
    ``hotpath_param_specs`` + ``build_program`` on the new context — runs
    the exact same admissibility gates it ran at startup, just with a
    smaller group: regimes legitimately flip (row-rs g=8 -> g=4, column
    -> replicated when ``n % g`` breaks), and PR 7's transpose pass
    restores the logical state onto whatever programs come out.
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = list(survivors)
    if not devs:
        raise ValueError("degraded_context: no surviving devices")
    dev = np.array(devs).reshape(1, len(devs))
    return MeshContext(mesh=Mesh(dev, ("data", "model")),
                       batch_axes=("data",))
