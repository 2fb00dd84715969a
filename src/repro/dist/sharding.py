"""Mesh sharding rules + in-model sharding hints.

Two jobs:

* **Hints** (``hint`` / ``set_hint_mesh``): models annotate activations with
  the mesh axes they should be partitioned over. Off-mesh (CPU tests, no
  hint mesh installed) every hint is the identity, so model code never
  branches on the execution environment. The dry-run installs its
  placeholder mesh around tracing.

* **Rules** (``ShardingRules`` via ``fed_rules`` / ``serve_rules``): map
  parameter / batch / cache pytrees to PartitionSpecs for the production
  meshes of ``launch.mesh``. Federated training shards the leading stacked
  client axis over the federated axes ("pod","data"); tensor-parallel
  shards the last dim of matrices over "model" where it divides. Serving
  drops the client axis and shards requests over "data".

Rules degrade to replication when an axis is absent or a non-client dim
does not divide — specs stay valid on any mesh, which is what lets one
codepath serve the single-pod, multi-pod, and interpret/CPU environments.
The one exception is the leading stacked *client* axis: a client count
that does not divide the mesh's client axes raises instead of silently
replicating N model copies onto every device.

Also home to the client-sharded superround placement helpers
(``client_mesh`` / ``fed_state_shardings`` / ``batch_block_sharding`` /
``mask_stack_sharding``) consumed by ``fed.engine``'s mesh execution path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig

PyTree = Any

_HINT_MESH = None


def set_hint_mesh(mesh) -> None:
    """Install (or clear, with None) the mesh that ``hint`` constrains to."""
    global _HINT_MESH
    _HINT_MESH = mesh


def hint_mesh():
    return _HINT_MESH


def _valid_member(mesh, member, dim_size: int):
    if member is None:
        return None
    names = member if isinstance(member, tuple) else (member,)
    if any(n not in mesh.axis_names for n in names):
        return None
    total = 1
    for n in names:
        total *= mesh.shape[n]
    return member if dim_size % total == 0 else None


def hint(x, *members):
    """with_sharding_constraint(x, P(*members)) under the hint mesh; identity
    off-mesh. Axes that are absent or don't divide degrade to replication."""
    mesh = _HINT_MESH
    if mesh is None:
        return x
    spec = P(*(_valid_member(mesh, m, d) for m, d in zip(members, x.shape)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def hint_data_groups() -> int:
    """MoE token groups = data-axis size of the hint mesh (1 off-mesh)."""
    mesh = _HINT_MESH
    if mesh is None or "data" not in mesh.axis_names:
        return 1
    return int(mesh.shape["data"])


def moe_ep_mode(num_experts: int) -> str:
    """Expert-parallel exchange mode for the MoE block.

    "none" keeps the per-group dispatch local (GSPMD handles any resharding;
    correct everywhere, and the only mode off-mesh). The explicit shard_map
    all-to-all path activates only on a real multi-device mesh whose data
    axis divides the expert count.
    """
    mesh = _HINT_MESH
    if mesh is None or "data" not in mesh.axis_names:
        return "none"
    ndev = int(mesh.shape["data"])
    if ndev <= 1 or num_experts % ndev:
        return "none"
    return "ep_data"


def moe_dispatch_exchange(buf_g, mode: str):
    """(G, E, C, d) group-major dispatch buffers -> (E, G*C, d) expert-major.

    The explicit all-to-all over the data axis (avoids GSPMD replicating the
    full buffer). Only reachable with a hint mesh installed.
    """
    if mode != "ep_data":
        raise ValueError(f"unknown ep mode: {mode}")
    mesh = _HINT_MESH
    if mesh is None:
        raise RuntimeError("moe_dispatch_exchange needs a hint mesh")

    g, e, c, d = buf_g.shape

    def body(buf):
        # buf: (G/P, E, C, d) per shard; exchange expert blocks across the
        # data axis: split E, concat G
        out = jax.lax.all_to_all(buf, "data", split_axis=1, concat_axis=0, tiled=True)
        ge, ee = out.shape[0], out.shape[1]
        return jax.numpy.moveaxis(out, 0, 1).reshape(ee, ge * c, d)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P("data", None, None, None),
        out_specs=P("data", None, None),
    )(buf_g)


def moe_combine_exchange(out_buf, flat_e_g, ranks_g, gates, mode: str, capacity: int):
    """Inverse of ``moe_dispatch_exchange`` + weighted combine."""
    if mode != "ep_data":
        raise ValueError(f"unknown ep mode: {mode}")
    mesh = _HINT_MESH
    if mesh is None:
        raise RuntimeError("moe_combine_exchange needs a hint mesh")
    import jax.numpy as jnp

    e, gc, d = out_buf.shape
    g = gc // capacity

    def body(buf):
        ee = buf.shape[0]
        back = jnp.moveaxis(buf.reshape(ee, g, capacity, d), 1, 0)  # (G, E/P, C, d)
        return jax.lax.all_to_all(back, "data", split_axis=0, concat_axis=1, tiled=True)

    out_g = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P("data", None, None),
        out_specs=P("data", None, None, None),
    )(out_buf)  # (G, E, C, d)

    tg, k = gates.shape[1], gates.shape[2]

    def combine_group(out_, flat_e_, ranks_, gates_):
        gathered = out_.at[flat_e_, ranks_].get(mode="fill", fill_value=0.0)
        return jnp.sum(
            gathered.reshape(tg, k, d).astype(jnp.float32) * gates_[..., None], axis=1
        )

    return jax.vmap(combine_group)(out_g, flat_e_g, ranks_g, gates)


# ---------------------------------------------------------------------------
# Pytree sharding rules
# ---------------------------------------------------------------------------

def _last_dim_member(mesh, shape, axis: str):
    if len(shape) < 2 or axis not in mesh.axis_names:
        return None
    return axis if shape[-1] % mesh.shape[axis] == 0 else None


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """PartitionSpec factory for one (config, mesh) pair.

    client_axes: mesh axes the leading stacked client dim is sharded over
    (empty for serving — params then carry no client axis).
    """

    mesh: Any
    client_axes: Tuple[str, ...] = ()
    model_axis: str = "model"
    data_axis: str = "data"

    def _client_member(self, dim_size: int):
        axes = tuple(a for a in self.client_axes if a in self.mesh.axis_names)
        if not axes:
            return None
        total = 1
        for a in axes:
            total *= self.mesh.shape[a]
        if dim_size % total:
            # a silent fall-back to replication here used to hide an N-fold
            # memory and compute blow-up behind an innocuous-looking config;
            # an indivisible client count is a topology mistake, not a hint
            raise ValueError(
                f"stacked client axis of size {dim_size} is not divisible by the "
                f"mesh's client axes {axes} ({total} ways); choose a client count "
                f"that divides the mesh (or drop the client axes from the "
                f"sharding rules) instead of silently replicating the state"
            )
        return axes if len(axes) > 1 else axes[0]

    def batch_spec(self, shape, *, has_accum: bool = False) -> P:
        """Training batch (accum?, N, micro/b, ...): client dim over the
        federated axes, everything else replicated."""
        members = [None] * len(shape)
        client_dim = 1 if has_accum else 0
        members[client_dim] = self._client_member(shape[client_dim])
        return P(*members)

    def request_spec(self, shape) -> P:
        """Serving request (B, ...): batch dim over "data" when it divides."""
        members = [None] * len(shape)
        if shape and self.data_axis in self.mesh.axis_names and shape[0] % self.mesh.shape[self.data_axis] == 0:
            members[0] = self.data_axis
        return P(*members)

    def _param_spec(self, shape) -> P:
        members = [None] * len(shape)
        if self.client_axes and shape:
            members[0] = self._client_member(shape[0])
        tp = _last_dim_member(self.mesh, shape, self.model_axis)
        if tp is not None and (not members or members[-1] is None) and len(shape) >= 2:
            members[-1] = tp
        return P(*members)

    def params_shardings(self, params: PyTree, *, scanned: bool = True) -> PyTree:
        del scanned  # specs are rank-generic; scan only adds a replicated dim
        return jax.tree_util.tree_map(
            lambda leaf: NamedSharding(self.mesh, self._param_spec(leaf.shape)), params
        )

    def caches_shardings(self, caches: PyTree, *, scanned: bool = True) -> PyTree:
        del scanned

        def spec(leaf):
            members = [None] * len(leaf.shape)
            if leaf.shape and self.data_axis in self.mesh.axis_names and leaf.shape[0] % self.mesh.shape[self.data_axis] == 0:
                members[0] = self.data_axis
            return NamedSharding(self.mesh, P(*members))

        return jax.tree_util.tree_map(spec, caches)


# ---------------------------------------------------------------------------
# Client-sharded superround placement (fed.engine's mesh execution path)
# ---------------------------------------------------------------------------


def client_axis_of(mesh) -> str:
    """The mesh axis the stacked client dim shards over: ``"clients"`` when
    present, else the mesh's first axis."""
    names = tuple(mesh.axis_names)
    return "clients" if "clients" in names else names[0]


def client_mesh(num_devices: int = 0, axis: str = "clients"):
    """A 1-D ``Mesh`` over the first ``num_devices`` local devices (0/None =
    all). The canonical mesh for the client-sharded superround engine."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    k = len(devs) if not num_devices else int(num_devices)
    if k < 1:
        raise ValueError(f"client mesh needs a positive device count, got {k}")
    if k > len(devs):
        raise ValueError(
            f"requested a {k}-device client mesh but only {len(devs)} device(s) "
            f"are visible; on CPU set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={k} before importing jax"
        )
    return Mesh(np.asarray(devs[:k]), (axis,))


def batch_block_sharding(mesh, axis: str) -> NamedSharding:
    """Superround batch blocks (κ₂, κ₁, N, b, ...): client dim over ``axis``."""
    return NamedSharding(mesh, P(None, None, axis))


def mask_stack_sharding(mesh, axis: str) -> NamedSharding:
    """Survival mask stacks (κ₂, N): client dim over ``axis``."""
    return NamedSharding(mesh, P(None, axis))


def fed_state_shardings(mesh, axis: str, state, stacked_dim: int):
    """NamedShardings for a placement-ordered stacked ``FedState``: leaves
    with the leading ``stacked_dim`` client axis shard over ``axis``, all
    else (step, rng, scalar opt leaves) replicates."""
    from repro.core.hierfavg import fed_state_partition_specs

    specs = fed_state_partition_specs(state, axis, stacked_dim)
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs, is_leaf=lambda x: isinstance(x, P)
    )


def canonical_state_shardings(mesh, axis: str, state, stacked_dim: int, rows: int):
    """NamedShardings for the canonical-order form (``rows`` clients) of a
    stacked ``FedState`` whose client leaves now lead with ``stacked_dim``:
    the client axis shards over ``axis`` when ``rows`` divides evenly, else
    the client leaves replicate over the mesh. Either way the state stays
    spread over the mesh instead of landing on one device."""
    if rows % int(mesh.shape[axis]) == 0:
        return fed_state_shardings(mesh, axis, state, stacked_dim)
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda _: rep, state)


def fed_rules(cfg: ArchConfig, mesh) -> ShardingRules:
    """Federated training: stacked client axis over ("pod","data")."""
    del cfg
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return ShardingRules(mesh=mesh, client_axes=axes)


def serve_rules(cfg: ArchConfig, mesh) -> ShardingRules:
    """Serving: no client axis; TP over "model", requests over "data"."""
    del cfg
    return ShardingRules(mesh=mesh, client_axes=())


def topology_for(cfg: ArchConfig, mesh):
    """The federated tree this config trains on this mesh: the uniform
    two-level FedTopology, or the FedPlan's ragged HierarchySpec when set."""
    num_pods = mesh.shape["pod"] if "pod" in mesh.axis_names else 1
    if cfg.fed.fanouts is not None:
        return cfg.fed.hierarchy(num_pods)
    from repro.core.hierfavg import FedTopology

    return FedTopology(
        num_edges=num_pods * cfg.fed.edges_per_pod,
        clients_per_edge=cfg.fed.clients_per_edge,
    )
