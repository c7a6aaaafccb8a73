"""MeshPlan: one composable description of the 3D parallelism layout.

Every layer that used to invent its own mesh — ``launch/mesh.py``'s
hard-coded 16-wide planes, ``distributed/context.py``'s self-built host
mesh, the training loop's bare ``context_parallel`` knob — now consumes a
single :class:`MeshPlan`: the per-axis sizes (``pod × data × seq × model``)
plus the device inventory they map onto.  The axes keep their logical roles
(DESIGN.md §Parallelism):

* ``pod``   — data parallelism across pods over DCN (slowest links);
* ``data``  — intra-pod FSDP: batch sharding + ZeRO-style weight sharding,
  and the plane the gradient psum rides;
* ``seq``   — context parallelism: activation length dims shard here and the
  Aaren ``(m, u, w)`` carry exchange / ring-flash rotation runs along it;
* ``model`` — tensor/expert parallelism on the fastest ICI links.

The paper's fixed-size per-layer state is what makes this composition
cheap: the ``seq``-axis payload is one carry per boundary (O(rows·(d+2))
floats), so it coexists with the gradient psum on ``data`` and the TP
collectives on ``model`` without competing for activation-sized bandwidth.

Size-1 axes stay *in* the mesh (except ``pod``, kept out when 1 so
single-pod mesh shapes — and every sharding spec derived from them — are
unchanged from the pre-plan code): the sharding rules then resolve their
logical names to no-op shardings and downstream specs stay mesh-shape
independent.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Per-axis sizes + device inventory for one composed mesh.

    ``devices``: optional explicit inventory (tuple of jax devices).  When
    ``None``, :meth:`build_mesh` takes the first ``total`` of
    ``jax.devices()`` — the plan stays importable/validatable without
    touching jax device state (device count locks at first jax init).
    """

    data: int = 1
    seq: int = 1
    model: int = 1
    pod: int = 1
    devices: tuple | None = None

    def __post_init__(self):
        for name in ("pod", "data", "seq", "model"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"MeshPlan.{name} must be an int >= 1, "
                                 f"got {v!r}")
        if self.devices is not None:
            object.__setattr__(self, "devices", tuple(self.devices))
            if len(self.devices) < self.total:
                raise ValueError(
                    f"MeshPlan {self.describe()} needs {self.total} devices, "
                    f"inventory has {len(self.devices)}")

    # ---- shape -----------------------------------------------------------

    @property
    def total(self) -> int:
        return self.pod * self.data * self.seq * self.model

    @property
    def is_trivial(self) -> bool:
        """Every axis size 1: no mesh/session needed at all."""
        return self.total == 1

    @property
    def axis_names(self) -> tuple[str, ...]:
        if self.pod > 1:
            return ("pod", "data", "seq", "model")
        return ("data", "seq", "model")

    @property
    def shape(self) -> tuple[int, ...]:
        if self.pod > 1:
            return (self.pod, self.data, self.seq, self.model)
        return (self.data, self.seq, self.model)

    def describe(self) -> str:
        return ("x".join(str(s) for s in self.shape)
                + " (" + " x ".join(self.axis_names) + ")")

    # ---- construction ----------------------------------------------------

    @classmethod
    def host(cls, *, data: int | None = None, seq: int = 1, model: int = 1,
             pod: int = 1, n_devices: int | None = None) -> "MeshPlan":
        """Plan over the host's devices; ``data=None`` soaks up the rest.

        The successor of the old ``make_host_mesh`` arithmetic: with an
        explicit ``data`` the product must not exceed the inventory; with
        ``data=None`` the device count must divide by ``pod·seq·model``.
        """
        if n_devices is None:
            import jax

            n_devices = len(jax.devices())
        denom = pod * seq * model
        if data is None:
            if n_devices % denom:
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"pod={pod} x seq={seq} x model={model}")
            data = n_devices // denom
        plan = cls(data=data, seq=seq, model=model, pod=pod)
        if plan.total > n_devices:
            raise ValueError(
                f"MeshPlan {plan.describe()} needs {plan.total} devices, "
                f"host has {n_devices}")
        return plan

    @classmethod
    def production(cls, *, multi_pod: bool = False, context_parallel: int = 1,
                   data_plane: int = 16, model: int = 16) -> "MeshPlan":
        """The dry-run cells' shape, derived instead of hard-coded.

        ``seq`` is carved out of the ``data_plane`` (carry exchanges are
        tiny but latency-sensitive, so they ride the same ICI links as FSDP
        traffic); ``context_parallel`` must divide the plane.
        """
        cp = context_parallel
        if data_plane % cp:
            raise ValueError(
                f"context_parallel={cp} must divide the {data_plane}-wide "
                "data plane")
        return cls(data=data_plane // cp, seq=cp, model=model,
                   pod=2 if multi_pod else 1)

    def build_mesh(self, devices=None):
        """Materialise the jax Mesh (first ``total`` devices row-major).

        Every axis is ``Auto``: the logical-axis rules steer GSPMD through
        sharding constraints, so arrays placed on the mesh must not carry
        their sharding in their types (``make_mesh`` defaults to
        ``Explicit``, under which plain ops like the embedding gather
        refuse sharded operands).
        """
        import jax
        from jax.sharding import AxisType

        devs = devices if devices is not None else self.devices
        if devs is None:
            devs = jax.devices()
        if len(devs) < self.total:
            raise ValueError(
                f"MeshPlan {self.describe()} needs {self.total} devices, "
                f"got {len(devs)}")
        return jax.make_mesh(self.shape, self.axis_names,
                             devices=list(devs)[:self.total],
                             axis_types=(AxisType.Auto,) * len(self.shape))

    # ---- accounting hooks ------------------------------------------------

    def axis_size(self, name: str) -> int:
        if name not in ("pod", "data", "seq", "model"):
            raise KeyError(name)
        return getattr(self, name)

    def exchange_rounds(self) -> int:
        """Log-step carry-exchange rounds along ``seq`` (fwd, per layer):
        one right-shift + ceil(log2 P) doubling rounds (DESIGN.md
        §Context-parallelism); 0 when the axis is trivial."""
        p = self.seq
        return 0 if p <= 1 else 1 + int(math.ceil(math.log2(p)))


def plan_from_mesh(mesh) -> MeshPlan:
    """Recover the plan view of an existing mesh (unknown axes rejected)."""
    shape = dict(mesh.shape)
    known = {"pod", "data", "seq", "model"}
    extra = set(shape) - known
    if extra:
        raise ValueError(f"mesh has non-plan axes {sorted(extra)}")
    devs = tuple(np.asarray(mesh.devices).reshape(-1))
    return MeshPlan(data=int(shape.get("data", 1)),
                    seq=int(shape.get("seq", 1)),
                    model=int(shape.get("model", 1)),
                    pod=int(shape.get("pod", 1)),
                    devices=devs)
