"""Serve-mesh seam tests: the strict single-device no-op, device
placement on real multi-device meshes, mesh routing, the mesh-aware
cost model, and the "engine/scheduler never branch on the mesh"
source-level contract.

The conformance behavior of the sharded backend itself (token
identity, preemption, sampling) lives in tests/test_serve_backend.py's
parametrized suite; this module pins the seam's mechanics.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.hwsim import DataflowConfig, simulate_model
from repro.models import model
from repro.serve import (
    EngineConfig,
    ArtemisCostModel,
    ServeEngine,
    ServeMesh,
    ShardedPagedBackend,
    Tracer,
    make_backend,
    make_serve_mesh,
)
from repro.serve.mesh import (describe_devices, init_params,
                               kv_pool_sharding, param_shardings, replicated)

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs 8 (simulated) devices")


def _cfg(arch="qwen3_8b"):
    return dataclasses.replace(configs.get_config(arch, smoke=True),
                               compute_dtype="float32")


def _engine(shards, arch="qwen3_8b", **overrides):
    cfg = _cfg(arch)
    kw = dict(page_size=8, n_pages=64, max_batch=3, max_pages_per_seq=8,
              prefill_chunk=8, cache_dtype="float32", mesh_shards=shards)
    kw.update(overrides)
    return ServeEngine(cfg, ecfg=EngineConfig(**kw), seed=0)


# ---------------------------------------------------------------------------
# the ServeMesh value + single-device no-op
# ---------------------------------------------------------------------------


def test_single_mesh_is_strict_noop():
    mesh = make_serve_mesh(1)
    assert mesh == ServeMesh()
    assert mesh.is_single and mesh.handle is None
    cfg = _cfg()
    params = model.init(jax.random.PRNGKey(0), cfg)
    # every placement helper is None: the single-device path never
    # device_puts, so it is bit-identical to the pre-mesh code
    assert param_shardings(mesh, cfg, params) is None
    assert kv_pool_sharding(mesh, cfg) is None
    assert replicated(mesh) is None


def test_mesh_validation():
    with pytest.raises(ValueError, match="n_shards"):
        make_serve_mesh(0)
    with pytest.raises(ValueError, match="n_shards"):
        ServeMesh(n_shards=0)
    # the handle-iff-multi invariant holds both ways
    with pytest.raises(ValueError, match="handle"):
        ServeMesh(n_shards=2)
    with pytest.raises(ValueError, match="handle"):
        ServeMesh(n_shards=1, handle=object())
    with pytest.raises(ValueError, match="mesh_shards"):
        EngineConfig(mesh_shards=0)


@needs8
def test_multi_mesh_carries_handle():
    mesh = make_serve_mesh(4)
    assert not mesh.is_single
    assert mesh.n_shards == 4 and mesh.axis == "model"
    assert mesh.handle is not None
    assert tuple(mesh.handle.axis_names) == ("model",)


# ---------------------------------------------------------------------------
# device placement
# ---------------------------------------------------------------------------


@needs8
def test_kv_pool_partitioned_on_heads_when_divisible():
    """smoke qwen3 has 2 KV heads: a 2-way mesh partitions the pool's
    KV-head axis (genuine per-shard K/V), an 8-way mesh replicates it
    (8 does not divide 2) and parallelism comes from the dataflow
    attention core instead."""
    eng2 = _engine(2)
    spec2 = eng2.backend.cache.kv["k"].sharding.spec
    assert tuple(spec2) == (None, None, None, "model", None)
    eng8 = _engine(8)
    spec8 = eng8.backend.cache.kv["k"].sharding.spec
    assert all(ax is None for ax in spec8)


@needs8
def test_init_params_creates_params_with_their_shardings():
    """The engine's own init creates each parameter with its TP
    sharding (one jitted init with out_shardings, no whole copy on one
    device first), with the same values as the unsharded init."""
    cfg = _cfg()
    key = jax.random.PRNGKey(3)
    mesh = make_serve_mesh(2)
    params = init_params(mesh, cfg, key)
    want = param_shardings(mesh, cfg, params)
    for leaf, sh in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(want)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
    single = init_params(make_serve_mesh(1), cfg, key)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(single)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@needs8
def test_kv_pool_created_with_its_sharding():
    eng = _engine(2)
    want = kv_pool_sharding(eng.mesh, eng.cfg)
    for arr in eng.backend.cache.kv.values():
        assert arr.sharding.is_equivalent_to(want, arr.ndim)
        assert not np.asarray(arr).any()


def test_describe_devices_reports_what_jax_sees():
    assert describe_devices() == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": jax.device_count()}


@needs8
def test_params_committed_to_mesh():
    eng = _engine(2)
    leaves = jax.tree_util.tree_leaves(eng.backend.params)
    assert any(
        any(ax == "model" for ax in leaf.sharding.spec)
        for leaf in leaves
        if hasattr(leaf.sharding, "spec")), \
        "no parameter carries a model-axis sharding on a 2-way mesh"


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


@needs8
def test_engine_threads_mesh_to_backend():
    eng = _engine(2)
    assert isinstance(eng.backend, ShardedPagedBackend)
    assert eng.mesh.n_shards == 2
    assert eng.backend.mesh is eng.mesh
    assert eng.cost.n_shards == 2


@needs8
def test_sharded_backend_rejects_single_mesh():
    cfg = _cfg()
    params = model.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="multi-shard"):
        ShardedPagedBackend(cfg, EngineConfig(), None, params,
                            Tracer(), lambda: 0.0,
                            mesh=make_serve_mesh(1))


@needs8
def test_slot_family_has_no_multidevice_backend():
    cfg = dataclasses.replace(configs.get_config("rwkv6_3b", smoke=True),
                              compute_dtype="float32")
    with pytest.raises(ValueError, match="no multi-device backend"):
        make_backend(cfg, EngineConfig(mesh_shards=2), None, None,
                     obs=Tracer(), clock=lambda: 0.0)


# ---------------------------------------------------------------------------
# shard observability
# ---------------------------------------------------------------------------


@needs8
def test_sharded_drain_emits_shard_metrics_and_trace_tracks():
    from repro.serve import to_chrome_trace, validate_chrome_trace
    eng = _engine(8, observability="trace")
    rng = np.random.default_rng(0)
    for n, g in ((5, 4), (11, 3)):
        eng.submit(rng.integers(2, eng.cfg.vocab_size, n).astype(np.int32),
                   max_new_tokens=g)
    eng.drain()
    reg = eng.obs.registry
    assert reg.gauge("backend/shard_count") == 8
    assert reg.count("backend/shard_steps") > 0
    assert reg.count("backend/shard_tokens") >= 5 + 11
    m = eng.backend.snapshot_metrics()
    assert m["n_shards"] == 8 and m["shard_steps"] > 0
    trace = to_chrome_trace(eng.events)
    validate_chrome_trace(trace)
    shard_slices = [e for e in trace["traceEvents"]
                    if e.get("cat") == "backend" and e.get("ph") == "X"]
    assert shard_slices, "no per-shard slices in the Chrome trace"
    assert {e["tid"] for e in shard_slices} == set(range(8))
    assert {e["args"]["n_shards"] for e in shard_slices} == {8}


# ---------------------------------------------------------------------------
# mesh-aware cost model
# ---------------------------------------------------------------------------


def test_cost_model_single_shard_bit_identical():
    """n_shards=1 must price EXACTLY like the pre-mesh cost model:
    the full-model workload with a zero collective term."""
    cfg = _cfg()
    base = ArtemisCostModel(cfg)
    assert base.n_shards == 1
    assert base._tp_collective(32) == (0.0, 0.0)
    ref = simulate_model(base._workload(32), DataflowConfig())
    assert base.price(32) == ref.latency_ns
    assert base.energy(32) == ref.energy_pj


def test_cost_model_shards_slice_the_workload():
    cfg = _cfg()   # n_heads=4, d_ff=128: both divide 4
    c4 = ArtemisCostModel(cfg, n_shards=4)
    w1, w4 = ArtemisCostModel(cfg)._workload(16), c4._workload(16)
    assert w4.n_heads == w1.n_heads // 4
    assert w4.d_ff == w1.d_ff // 4
    assert w4.params == pytest.approx(w1.params / 4)
    # indivisible head counts stay whole (replicated on device too)
    w3 = ArtemisCostModel(cfg, n_shards=3)._workload(16)
    assert w3.n_heads == w1.n_heads
    assert w3.params == pytest.approx(w1.params / 3)


def test_cost_model_prices_the_all_reduce():
    """The TP collective term follows the ring all-reduce formula over
    the hwsim link model and grows with tokens and layers."""
    cfg = _cfg()
    c8 = ArtemisCostModel(cfg, n_shards=8)
    lat, energy = c8._tp_collective(32)
    assert lat > 0.0 and energy > 0.0
    from repro.hwsim import DramGeometry
    geom = DramGeometry(DataflowConfig().hw)
    ring_bits = 2.0 * 7 / 8 * (32 * cfg.d_model * 32)
    assert lat == pytest.approx(
        2 * cfg.n_layers * geom.transfer_latency_ns(ring_bits))
    assert energy == pytest.approx(
        2 * cfg.n_layers * geom.transfer_energy_pj(ring_bits) * 8)
    # the term is part of the public price, monotone in tokens
    assert c8.price(32) == pytest.approx(
        c8._simulate(32).latency_ns + lat)
    assert c8._tp_collective(64)[0] > lat
    with pytest.raises(ValueError, match="n_shards"):
        ArtemisCostModel(cfg, n_shards=0)


# ---------------------------------------------------------------------------
# engine/scheduler stay mesh-oblivious (source-level contract)
# ---------------------------------------------------------------------------


def test_engine_and_scheduler_have_no_mesh_branches():
    """The tentpole's design constraint: the mesh is threaded as a
    VALUE (engine builds it once and hands it to make_backend); neither
    engine.py nor scheduler.py may branch on mesh state or name the
    sharded backend."""
    import repro.serve.engine as eng_mod
    import repro.serve.scheduler as sched_mod
    import inspect
    eng_src = inspect.getsource(eng_mod)
    sched_src = inspect.getsource(sched_mod)
    # the engine may PASS mesh values (make_serve_mesh / n_shards=...)
    # but never inspect them; the scheduler never sees the mesh at all
    for banned in ("is_single", "ShardedPagedBackend"):
        assert banned not in eng_src, f"engine.py references {banned}"
        assert banned not in sched_src, f"scheduler.py references {banned}"
    for banned in ("mesh", "n_shards"):
        assert banned not in sched_src, f"scheduler.py references {banned}"
