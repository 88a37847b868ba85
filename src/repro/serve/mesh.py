"""Device-mesh seam for the serve stack.

Every serve-layer component that could care about device placement —
backend construction, parameter/KV placement, cost pricing — is
parameterized by a `ServeMesh` instead of asking jax about devices.
This module is the ONE place the serve tree is allowed to construct a
mesh or query the device inventory (statically enforced by the
`mesh-discipline` rule in `repro.analysis`); everything downstream
takes the seam as a value.

Two invariants the refactor hangs on:

  * The single-device mesh (`make_serve_mesh(1)`, the default) is a
    strict no-op: it constructs NO jax objects, performs NO device
    queries, and every placement helper below returns None — so the
    single-device serve path is bit-identical to the pre-mesh code.
  * A multi-shard mesh is pure tensor parallelism over one axis
    (`"model"`): parameters shard per `parallel.sharding.param_specs`
    (FSDP off — there is no data axis), the paged KV pool shards along
    the KV-head axis when it divides (`paged_pool_spec`), and page
    tables stay host-side, so the allocator / PrefixIndex / COW logic
    is mesh-oblivious.

Development and CI simulate the mesh on CPU:
`XLA_FLAGS=--xla_force_host_platform_device_count=8`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

__all__ = [
    "ServeMesh",
    "make_serve_mesh",
    "describe_devices",
    "init_params",
    "param_shardings",
    "kv_pool_sharding",
    "replicated",
    "replicated_spec",
    "seq_sharded_spec",
]


@dataclasses.dataclass(frozen=True)
class ServeMesh:
    """The serve layer's view of device topology.

    n_shards  tensor-parallel degree (1 = single device)
    axis      mesh axis name the TP collectives run over
    handle    the jax.sharding.Mesh when n_shards > 1, else None —
              the single-device seam never touches jax device state
    """
    n_shards: int = 1
    axis: str = "model"
    handle: Any = None

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if (self.handle is None) != (self.n_shards == 1):
            raise ValueError(
                "ServeMesh invariant: handle is None iff n_shards == 1 "
                f"(got n_shards={self.n_shards}, handle={self.handle!r})")

    @property
    def is_single(self) -> bool:
        return self.n_shards == 1


def make_serve_mesh(n_shards: int = 1, axis: str = "model") -> ServeMesh:
    """Build the serve mesh. n_shards == 1 is the strict no-op default."""
    if n_shards == 1:
        return ServeMesh()
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    import jax
    from jax.sharding import AxisType
    try:
        handle = jax.make_mesh((n_shards,), (axis,),
                               axis_types=(AxisType.Auto,))
    except ValueError as e:
        raise ValueError(
            f"cannot build a {n_shards}-way serve mesh: {e}. On CPU, "
            f"simulate devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} "
            f"(set BEFORE jax initializes)") from e
    return ServeMesh(n_shards=n_shards, axis=axis, handle=handle)


def describe_devices() -> dict:
    """The device inventory as JAX reports it: platform and
    `device_kind` of device 0, and the device count."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


# ---------------------------------------------------------------------------
# placement helpers — all return None on the single-device mesh so the
# default path stays a strict no-op
# ---------------------------------------------------------------------------


def param_shardings(mesh: ServeMesh, cfg, params):
    """NamedSharding pytree for the model parameters (pure TP: the
    `parallel.sharding` rules with FSDP off), or None on the single
    mesh."""
    if mesh.is_single:
        return None
    from repro.parallel import sharding as sh
    rules = sh.ShardingRules(fsdp=False)
    specs = sh.param_specs(cfg, params, mesh.handle, rules)
    return sh.named(mesh.handle, specs)


def init_params(mesh: ServeMesh, cfg, key):
    """Random parameters from `key`, created where they live: one jitted
    `model.init` whose outputs carry `param_shardings` on a multi-shard
    mesh, so no device ever holds the whole model (a 36-layer 8B model
    fits four chips but not one). On the single mesh the jitted init
    places everything on the default device — jitted so the random
    draws fuse instead of holding per-leaf temporaries."""
    import functools

    import jax
    from repro.models import model
    init = functools.partial(model.init, cfg=cfg)
    shardings = param_shardings(mesh, cfg, jax.eval_shape(init, key))
    return jax.jit(init, out_shardings=shardings)(key)


def kv_pool_sharding(mesh: ServeMesh, cfg):
    """NamedSharding for the paged KV pool (L, n_pages, page, KV, hd):
    per-shard K/V partitioned along heads when KV heads divide the TP
    degree, replicated otherwise. None on the single mesh."""
    if mesh.is_single:
        return None
    import jax
    from repro.parallel import sharding as sh
    spec = sh.paged_pool_spec(cfg, mesh.handle)
    return jax.sharding.NamedSharding(mesh.handle, spec)


def replicated(mesh: ServeMesh):
    """Fully-replicated NamedSharding over the mesh, or None on the
    single mesh."""
    if mesh.is_single:
        return None
    import jax
    from jax.sharding import PartitionSpec
    return jax.sharding.NamedSharding(mesh.handle, PartitionSpec())


def replicated_spec(mesh: ServeMesh):
    """Bare replicated PartitionSpec for shard_map in/out specs (the
    sharded backend's attention cores), or None on the single mesh.
    Consumers take specs from here instead of constructing them — the
    `shard-spec-discipline` analysis rule enforces it."""
    if mesh.is_single:
        return None
    from jax.sharding import PartitionSpec
    return PartitionSpec()


def seq_sharded_spec(mesh: ServeMesh):
    """PartitionSpec sharding axis 1 — the SEQUENCE axis of a gathered
    (batch, seq, ...) KV view — over the mesh's TP axis, or None on
    the single mesh. This is the token-dataflow layout the dataflow
    attention cores (`ring_attention` / `split_kv_attention`) consume."""
    if mesh.is_single:
        return None
    from jax.sharding import PartitionSpec
    return PartitionSpec(None, mesh.axis)
