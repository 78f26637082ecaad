"""Trace-time mesh context.

Model code is mesh-agnostic except where it *must* name axes (the
shard_map'd expert-parallel MoE path).  The step builders install a
:class:`MeshContext` for the duration of tracing; model code reads it
through :func:`mesh_context`.  When no context is installed (unit tests,
pure-CPU smoke runs) the models fall back to their mesh-free paths.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax

_STATE = threading.local()


@dataclasses.dataclass(frozen=True)
class MeshContext:
    mesh: jax.sharding.Mesh
    dp_axes: tuple[str, ...] = ("data",)     # batch-parallel mesh axes
    ep_axis: str = "model"                   # expert-parallel mesh axis
    fsdp_axis: str = "data"                  # parameter-shard (ZeRO-3) axis
    rules: object = None                     # RuleTable for activation hints

    @property
    def all_axes(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)


def shard_hint(x, logical_axes: tuple[str | None, ...]):
    """Activation sharding constraint by LOGICAL axis names.

    The Megatron/MaxText discipline: models annotate where activations
    live ("batch" on the data axes, "heads"/"mlp" on the model axis,
    everything else replicated), and GSPMD then picks weight-gather
    (ZeRO-3) over activation all-reduce.  No-op without a mesh context
    (CPU unit tests) or when a dim is not divisible by its mesh axes.
    """
    ctx = mesh_context()
    if ctx is None or ctx.rules is None:
        return x
    spec = ctx.rules.spec_for(tuple(logical_axes), tuple(x.shape), ctx.mesh)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, spec))


def mesh_context() -> MeshContext | None:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def use_mesh_context(ctx: MeshContext):
    """Install the thread-local context.  Step builders enter it inside
    their traced bodies; model code passes ``ctx.mesh`` to shard_map
    explicitly, so JAX's ambient mesh is never needed."""
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = ctx
    try:
        yield ctx
    finally:
        _STATE.ctx = prev
