"""The mesh/layout contract: ``Runtime`` + the ``P`` partition-spec alias.

Every model/train/serve/data module programs against *logical* axis names:

* ``"fsdp"`` — the data/ZeRO axes (batch sharding + parameter sharding);
  may span several mesh axes (multi-pod: ``("pod", "data")``).
* ``"tp"``   — the tensor-parallel (model) axis; resolves to nothing when
  TP is disabled or the mesh has no model axis.

``Runtime`` resolves those names to the concrete mesh, applies the
divide-or-replicate rule (an axis entry is dropped when the dimension is
not divisible by the axis size — GSPMD would otherwise pad), and degrades
to single-device no-ops when ``mesh=None`` so the same model code runs
everywhere from a laptop CPU to a multi-pod dry-run.

Layout knobs (all recorded in the frozen dataclass so a Runtime value
fully determines the compiled program):

* ``tp_disabled``      — pure-FSDP relayout: the model axis is folded into
  the data axes (``rt.fsdp_size`` grows, ``rt.tp`` reports ``False``).
* ``sequence_parallel``— shard the residual stream's sequence dim over the
  model axis between blocks.
* ``moe_mode``         — ``"tp"`` (sharded-FFN experts) or ``"ep"``
  (all_to_all expert parallelism — the paper's adversarial pattern).
* ``seq_sharded_decode`` — decode-time KV/latent caches sharded over the
  model axis on the sequence dim (LSE-combined partial attention).
* ``collective_dtype`` — wire dtype for gradient reductions.
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["P", "Runtime", "host_device_runtime"]

# Logical entry names understood by spec()/spec_div()/shard().
_FSDP = "fsdp"
_TP = "tp"

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}

SpecEntry = Union[None, str]


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Frozen distribution contract: mesh + logical layout knobs."""

    mesh: Optional[Mesh] = None
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    tp_disabled: bool = False
    sequence_parallel: bool = False
    moe_mode: str = "tp"                  # tp | ep
    seq_sharded_decode: bool = True
    collective_dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "data_axes", tuple(self.data_axes))
        if self.mesh is not None:
            names = set(self.mesh.axis_names)
            missing = [a for a in self.data_axes if a not in names]
            if missing:
                raise ValueError(f"data_axes {missing} not in mesh axes "
                                 f"{tuple(self.mesh.axis_names)}")
        if self.moe_mode not in ("tp", "ep"):
            raise ValueError(f"moe_mode must be 'tp' or 'ep', "
                             f"got {self.moe_mode!r}")
        if self.collective_dtype not in _DTYPES:
            raise ValueError(f"collective_dtype must be one of "
                             f"{sorted(_DTYPES)}, got "
                             f"{self.collective_dtype!r}")

    # ---- axis resolution -----------------------------------------------------
    @functools.cached_property
    def _mesh_sizes(self) -> dict:
        return dict(self.mesh.shape) if self.mesh is not None else {}

    @functools.cached_property
    def fsdp_axes(self) -> Tuple[str, ...]:
        """The mesh axes acting as data/ZeRO axes.  With ``tp_disabled``
        the model axis is folded in (pure-FSDP relayout on the same
        physical mesh), whether or not the caller listed it."""
        axes = self.data_axes
        if (self.tp_disabled and self.model_axis in self._mesh_sizes
                and self.model_axis not in axes):
            axes = axes + (self.model_axis,)
        return axes

    @functools.cached_property
    def fsdp_size(self) -> int:
        if self.mesh is None:
            return 1
        return functools.reduce(
            operator.mul, (self._mesh_sizes[a] for a in self.fsdp_axes), 1)

    @functools.cached_property
    def tp_size(self) -> int:
        if (self.mesh is None or self.tp_disabled
                or self.model_axis in self.fsdp_axes):
            return 1
        return int(self._mesh_sizes.get(self.model_axis, 1))

    @property
    def fsdp(self):
        """Spec entry for the data axes: axis name, tuple of names, or
        None on a single device — usable directly inside ``P(...)``."""
        if self.mesh is None:
            return None
        axes = self.fsdp_axes
        return axes if len(axes) > 1 else axes[0]

    @property
    def tp(self):
        """Spec entry for the model axis when TP is active; reports
        ``False`` otherwise (never place the disabled value in a P — the
        resolvers below map ``"tp"`` to None for you)."""
        return self.model_axis if self.tp_size > 1 else False

    def _resolve(self, entry: SpecEntry):
        if entry is None:
            return None
        if entry == _FSDP:
            return self.fsdp
        if entry == _TP:
            return self.tp or None
        # raw mesh-axis name: pass through if it exists, else replicate
        return entry if entry in self._mesh_sizes else None

    def _entry_size(self, entry: SpecEntry) -> int:
        if entry is None:
            return 1
        if entry == _FSDP:
            return self.fsdp_size
        if entry == _TP:
            return self.tp_size
        return int(self._mesh_sizes.get(entry, 1))

    # ---- spec builders -------------------------------------------------------
    def spec(self, *entries: SpecEntry) -> P:
        """PartitionSpec from logical entries (no divisibility check)."""
        return P(*(self._resolve(e) for e in entries))

    def spec_div(self, entries: Sequence[SpecEntry],
                 shape: Sequence[int]) -> P:
        """PartitionSpec with the divide-or-replicate rule: an entry is
        kept only when the matching dimension is divisible by its axis
        size (and the axis is real, i.e. size > 1)."""
        if len(entries) != len(shape):
            raise ValueError(f"entries {entries!r} vs shape {shape!r}")
        out = []
        for e, d in zip(entries, shape):
            size = self._entry_size(e)
            out.append(self._resolve(e)
                       if size > 1 and int(d) % size == 0 else None)
        return P(*out)

    # ---- array placement -----------------------------------------------------
    def shard(self, x, *entries: SpecEntry):
        """Sharding constraint by logical entries (divide-or-replicate);
        identity on a single device."""
        if self.mesh is None:
            return x
        return self.shard_spec(x, self.spec_div(entries, x.shape))

    def shard_spec(self, x, spec: P):
        """Sharding constraint with an explicit PartitionSpec; identity on
        a single device."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def tree_sharding(self, spec_tree):
        """PartitionSpec tree -> NamedSharding tree (None without a mesh,
        which ``jax.jit``'s in_shardings accepts as "let XLA choose")."""
        if self.mesh is None:
            return None
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda s: isinstance(s, P))

    def shard_map(self, f, *, in_specs, out_specs, check_vma: bool = False):
        """``jax.shard_map`` over this runtime's mesh; identity wrapper on
        a single device (the body then sees the global arrays)."""
        if self.mesh is None:
            return f
        return jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)

    # ---- misc ----------------------------------------------------------------
    def astype(self, x):
        """Cast to the collective wire dtype (``collective_dtype``)."""
        return x.astype(_DTYPES[self.collective_dtype])


def host_device_runtime(devices: Optional[int] = None,
                        axis: str = "data") -> Runtime:
    """A :class:`Runtime` over a 1-D mesh of ``devices`` local devices:
    the chips of an accelerator host, or forced host CPU devices under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

    * ``devices`` ``None`` -> use every visible device;
    * ``devices <= 1``     -> ``Runtime(mesh=None)`` (single-device
      no-op degradation, same program, no shard_map);
    * asking for more devices than jax can see raises.
    """
    avail = jax.device_count()
    n = avail if devices is None else int(devices)
    if n <= 1:
        return Runtime(mesh=None, data_axes=(axis,))
    if n > avail:
        raise RuntimeError(
            f"asked for {n} devices but jax sees {avail} "
            f"({jax.default_backend()}).  On an accelerator host, ask for "
            f"at most the chips it has.  To rehearse on the CPU, set "
            f"JAX_PLATFORMS=cpu and "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"jax initializes, or launch via "
            f"`JAX_PLATFORMS=cpu python -m repro.experiments sweep "
            f"--devices {n}`, which sets the flag for you.")
    mesh = Mesh(np.asarray(jax.devices()[:n]), (axis,))
    return Runtime(mesh=mesh, data_axes=(axis,))
